"""Pallas TPU kernel: OPIMA bit-sliced (nibble-plane) integer matmul.

This is the paper's PIM datapath adapted to the TPU memory hierarchy:
weight nibbles live in VMEM tiles (the "subarray"), each
(act-plane, weight-plane) pair is a one-shot MXU matmul over the K block
(the "WDM accumulation"), and the shift-and-add recombination (the
"aggregation unit") happens in the int32 VMEM accumulator.

Two entry points:
  * ``pim_matmul_pallas``        — raw int32 accumulator output.
  * ``pim_matmul_fused_pallas``  — adds the aggregation unit's *fused
    dequantization epilogue*: on the last K step the int32 accumulator
    tile is rescaled in VMEM by the per-row activation scale and the
    per-column weight scale (+ optional bias) and written out as float32,
    so the accumulator never round-trips through a separate float pass.
    The epilogue applies ``(acc * a_scale) * w_scale (+ bias)`` in float32
    with the same broadcast order as the jnp reference. The dequantized
    (no-bias) output is bit-identical to the eager jnp reference; the
    optional bias add compiles to a fused multiply-add (XLA contracts the
    trailing ``mul+add`` into an FMA — one rounding instead of two, i.e.
    at least as accurate as the eager two-step reference, within 1 ulp).

Padding and tiling are two separate contracts:
  * ``plan_padding`` is the layout weights are programmed into once
    (:func:`repro.core.pim.plan_from_qtensor`): K to a multiple of
    ``PLAN_BK``, N to a multiple of ``PLAN_BN``. The analog substrates,
    fault injection and im2col's zero tail all read that layout.
  * ``kernel_tiles`` picks, per call, the (bm, bn, bk) the grid runs on
    from the issued (M, Kp, Np): tiles that divide Kp and Np, the whole
    K in one step where it fits, under ``TILE_BUDGET`` of VMEM. A grid
    step has a fixed cost (~0.2 us on a v5e, fitted to the kernel's
    measured times), so few large steps beat many small ones on the
    CNN's million-row im2col GEMMs.

  grid = (cdiv(M, bm), Np/bn, Kp/bk); K is the innermost (sequential)
  axis so each (m, n) output tile accumulates across K steps in a VMEM
  scratch accumulator, written out on the last K step. Activation
  planes are never padded along M: the last M block may be partial, and
  its rows beyond M only reach output rows that Pallas does not write.
  Plane pairs are unrolled inside the kernel body (Pa, Pw <= 2: 4b/8b
  operands).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The plan's padding: K to whole 512-deep blocks, N to whole 128 lanes.
PLAN_BK = 512
PLAN_BN = 128

# Mosaic register-tile geometry for float32 operands: the fused epilogue's
# scale vectors are padded to full (sublane, lane) tiles so compiled
# lowering never sees a width-1 minor axis (interpret mode accepts those;
# real-TPU Mosaic wants lane-aligned operands).
LANE = 128
SUBLANE = 8
# int8 packs four rows per sublane: an int8 block's row count is a
# multiple of 32 unless it spans the whole dim
SUBLANE_I8 = 32

# Call tiles: rows per M tile and columns per N tile at most. Past ~2k
# rows a step's DMA (~2.6 us for one 2048 x 1024 int8 plane block at
# 819 GB/s) already dwarfs its fixed cost.
MAX_BM = 2048
MAX_BN = 512
# Scoped VMEM the kernel asks of Mosaic (v5e: 128 MiB per core, 16 MiB
# scoped by default), and the share of it the chooser lets the blocks
# take; the rest is Mosaic's own (dot results, internal scratch).
VMEM_LIMIT = 48 * 2 ** 20
TILE_BUDGET = 24 * 2 ** 20


def _up(v: int, b: int) -> int:
    return -(-v // b) * b


def plan_padding(k: int, n: int) -> Tuple[int, int]:
    """(Kp, Np) the plan pads a (K, N) weight to: K to a multiple of
    ``PLAN_BK`` (or K itself below that), N to a multiple of ``PLAN_BN``
    (or N itself below that). Idempotent on its own output."""
    return _up(k, min(PLAN_BK, k)), _up(n, min(PLAN_BN, n))


def tile_vmem_bytes(bm: int, bn: int, bk: int, pa: int, pw: int,
                    want_rowsum: bool = False) -> int:
    """VMEM the fused kernel's blocks take at tiles (bm, bn, bk): the
    plane, scale, bias and output blocks double-buffered, the int32
    accumulator once, each padded to whole (sublane, lane) tiles."""
    rows, lanes = _up(bm, SUBLANE), _up(bn, LANE)
    planes = (pa * _up(bm, SUBLANE_I8) * _up(bk, LANE)
              + pw * _up(bk, SUBLANE_I8) * lanes)
    scales = rows * LANE * 4 + 2 * SUBLANE * lanes * 4
    out = rows * lanes * 4 + (rows * LANE * 4 if want_rowsum else 0)
    return 2 * (planes + scales + out) + rows * lanes * 4


def _largest_divisor(v: int, step: int, cap: int) -> Optional[int]:
    """Largest multiple of ``step`` that divides ``v`` and is at most
    ``cap``; None where there is none."""
    return next((d for d in range(cap - cap % step, 0, -step)
                 if v % d == 0), None)


def _row_tile(m: int, cap: int) -> int:
    """M tile of at most ``cap`` rows: M itself when it fits, else the
    largest divisor of M that is a multiple of 32 and at least half the
    cap, else equal tiles rounded up to 32 rows (the last one partial)."""
    if m <= cap:
        return m
    d = _largest_divisor(m, SUBLANE_I8, cap)
    if d and 2 * d >= cap:
        return d
    return _up(-(-m // -(-m // cap)), SUBLANE_I8)


def kernel_tiles(m: int, kp: int, np_: int, pa: int = 1, pw: int = 1,
                 want_rowsum: bool = False) -> Tuple[int, int, int]:
    """(bm, bn, bk) for one call on issued (M, Kp, Np), Kp and Np as
    ``plan_padding`` leaves them.

    * bn: the whole Np up to ``MAX_BN``, else its largest divisor that is
      a multiple of 128 and at most ``MAX_BN``.
    * bk: the whole Kp where a 512-row tile (or all of M) fits the
      budget, else the largest multiple of 512 dividing Kp that fits.
    * bm: ``_row_tile`` of M under the largest cap (2048 halved) that
      fits the budget at that bk.

    ``plan_padding`` leaves Np above 512 a multiple of 128 and Kp above
    512 a multiple of 512, so both divisors exist. Every block's last two
    dims are then multiples of (32, 128) for int8 and (8, 128) for f32,
    or the whole dim, as Mosaic requires."""
    bn = np_ if np_ <= MAX_BN else _largest_divisor(np_, LANE, MAX_BN)

    def fits(rows: int, bk: int) -> bool:
        return tile_vmem_bytes(min(m, rows), bn, bk, pa, pw,
                               want_rowsum) <= TILE_BUDGET

    ks = [kp] + [d for d in range(kp - PLAN_BK, 0, -PLAN_BK)
                 if kp % d == 0]
    bk = next((d for d in ks if fits(MAX_BM // 4, d)), min(kp, PLAN_BK))
    cap = MAX_BM
    while cap > SUBLANE_I8 and not fits(cap, bk):
        cap //= 2
    return _row_tile(m, cap), bn, bk


def issued_tiles(m: int, k: int, n: int, pa: int, pw: int,
                 want_rowsum: bool = False, bm: Optional[int] = None,
                 bn: Optional[int] = None, bk: Optional[int] = None
                 ) -> Tuple[int, int, int, int, int]:
    """What a kernel wrapper issues for planes (Pa, M, K) x (Pw, K, N):
    (bm, bn, bk, Kp, Np), tiles from ``kernel_tiles`` unless given, and K
    and N padded to the plan's layout and then to whole tiles."""
    kp, np_ = plan_padding(k, n)
    tm, tn, tk = kernel_tiles(m, kp, np_, pa, pw, want_rowsum)
    bm, bn, bk = min(bm or tm, m), min(bn or tn, np_), min(bk or tk, kp)
    return bm, bn, bk, _up(kp, bk), _up(np_, bn)


def _plane_pairs_acc(a_ref, w_ref, acc, pa: int, pw: int):
    """Add every (act-plane, weight-plane) product of this K step to
    ``acc``. Each pair is one int8 x int8 -> int32 MXU matmul (nibbles lie
    in [-8, 7], so the int32 accumulator is exact) plus a static shift;
    plane pairs are unrolled."""
    for d in range(pa):
        for e in range(pw):
            partial = jax.lax.dot_general(
                a_ref[d], w_ref[e], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc = acc + partial * (16 ** (d + e))
    return acc


def _pad_planes(a_planes, w_planes, kp: int, np_: int):
    """Zero-pad the planes along K and N to the issued Kp, Np (exact for
    an integer matmul); never along M. Planes from a plan arrive padded
    already, so this copies only for direct callers."""
    k, n = w_planes.shape[1:]
    if kp > k:
        a_planes = jnp.pad(a_planes, ((0, 0), (0, 0), (0, kp - k)))
    if kp > k or np_ > n:
        w_planes = jnp.pad(w_planes, ((0, 0), (0, kp - k), (0, np_ - n)))
    return a_planes, w_planes


def _pim_matmul_kernel(a_ref, w_ref, o_ref, acc_ref, *, n_k: int,
                       pa: int, pw: int):
    """One (m, n, k) grid step.

    a_ref: (Pa, bm, bk) int8  — activation nibble planes tile
    w_ref: (Pw, bk, bn) int8  — weight nibble planes tile
    o_ref: (bm, bn) int32     — output tile (written at last k step)
    acc_ref: (bm, bn) int32   — VMEM accumulator scratch
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = _plane_pairs_acc(a_ref, w_ref, acc_ref[...], pa, pw)

    @pl.when(k_step == n_k - 1)
    def _write_out():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def pim_matmul_pallas(a_planes: jax.Array, w_planes: jax.Array,
                      bm: Optional[int] = None, bn: Optional[int] = None,
                      bk: Optional[int] = None,
                      interpret: bool = False) -> jax.Array:
    """Bit-sliced integer matmul via pallas_call.

    Args:
      a_planes: (Pa, M, K) int8 nibble planes of the activations.
      w_planes: (Pw, K, N) int8 nibble planes of the weights.
      bm/bn/bk: VMEM tile sizes; ``kernel_tiles`` picks those not given.
      interpret: run in interpreter mode (CPU validation).

    Returns:
      (M, N) int32 — bit-exact vs. ref.pim_matmul_ref.
    """
    pa, m, k = a_planes.shape
    pw, k2, n = w_planes.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"

    bm, bn, bk, kp, np_ = issued_tiles(m, k, n, pa, pw, bm=bm, bn=bn,
                                       bk=bk)
    a_planes, w_planes = _pad_planes(a_planes, w_planes, kp, np_)
    n_k = kp // bk

    out = pl.pallas_call(
        functools.partial(_pim_matmul_kernel, n_k=n_k, pa=pa, pw=pw),
        grid=(pl.cdiv(m, bm), np_ // bn, n_k),
        in_specs=[
            pl.BlockSpec((pa, bm, bk), lambda i, j, s: (0, i, s)),
            pl.BlockSpec((pw, bk, bn), lambda i, j, s: (0, s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, np_), jnp.int32),
        # int32 accumulator tile, persistent across the sequential K axis
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="pim_matmul_int",
    )(a_planes, w_planes)
    return out[:, :n]


def _pim_matmul_fused_kernel(a_ref, w_ref, as_ref, ws_ref, *rest, n_k: int,
                             pa: int, pw: int, has_bias: bool,
                             want_rowsum: bool):
    """One (m, n, k) grid step with the fused dequant epilogue.

    a_ref: (Pa, bm, bk) int8  — activation nibble planes tile
    w_ref: (Pw, bk, bn) int8  — weight nibble planes tile
    as_ref: (bm, LANE) f32    — per-row activation scales, value in lane 0
    ws_ref: (SUBLANE, bn) f32 — per-column weight scales, value in row 0
    [b_ref]                   — optional bias, same layout as ws_ref
    o_ref: (bm, bn) f32       — dequantized output tile (last k step)
    [rs_ref]: (bm, LANE) i32  — this (i, j) tile's accumulator row-sum
                                partial for ABFT (value replicated
                                across lanes), written once at the last
                                k step; the caller folds the j-block
                                partials. Keeping the block private per
                                (i, j) — instead of accumulating into a
                                revisited (i, 0) block — keeps the
                                row-sum out of the grid's critical path
                                (~13% whole-kernel tax measured on the
                                revisited form).
    acc_ref: (bm, bn) int32   — VMEM accumulator scratch
    """
    rest = list(rest)
    b_ref = rest.pop(0) if has_bias else None
    o_ref = rest.pop(0)
    rs_ref = rest.pop(0) if want_rowsum else None
    acc_ref = rest.pop(0)
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = _plane_pairs_acc(a_ref, w_ref, acc_ref[...], pa, pw)

    @pl.when(k_step == n_k - 1)
    def _write_out():
        # Same op order as the jnp path: (acc * a_scale) * w_scale (+ bias),
        # elementwise in f32 — bit-identical dequantization.
        if want_rowsum:
            # int32 wraparound row-sum of this N tile; lanes all carry the
            # same value so the caller can read lane 0 without a relayout
            tile_rs = jnp.sum(acc_ref[...], axis=1, keepdims=True)
            rs_ref[...] = jnp.broadcast_to(tile_rs, rs_ref.shape)
        a_s = as_ref[...][:, :1]            # (bm, 1): value lives in lane 0
        w_s = ws_ref[...][:1, :]            # (1, bn): value lives in row 0
        out = acc_ref[...].astype(jnp.float32) * a_s * w_s
        if has_bias:
            out = out + b_ref[...][:1, :]
        o_ref[...] = out


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret",
                                    "want_rowsum"))
def pim_matmul_fused_pallas(a_planes: jax.Array, w_planes: jax.Array,
                            a_scale: jax.Array, w_scale: jax.Array,
                            bias: Optional[jax.Array] = None,
                            bm: Optional[int] = None,
                            bn: Optional[int] = None,
                            bk: Optional[int] = None,
                            interpret: bool = False,
                            want_rowsum: bool = False):
    """Bit-sliced integer matmul with the fused dequantization epilogue.

    Args:
      a_planes: (Pa, M, K) int8 nibble planes of the activations.
      w_planes: (Pw, K, N) int8 nibble planes of the weights.
      a_scale: (M, 1) f32 per-row dynamic activation scales.
      w_scale: (1, N) f32 per-column weight scales.
      bias: optional (1, N) f32, added after dequantization.
      bm/bn/bk: VMEM tile sizes; ``kernel_tiles`` picks those not given.
      interpret: run in interpreter mode (CPU validation).
      want_rowsum: also emit the (M,) int32 accumulator row-sums from
        the epilogue (ABFT checksum verification input). Zero-padded
        columns contribute nothing, so the row-sum over the padded tile
        equals the row-sum over the first N columns exactly.

    Returns:
      (M, N) float32 — bit-exact vs. ref.pim_matmul_fused_ref — or a
      ``(out, rowsum)`` pair when ``want_rowsum``.
    """
    pa, m, k = a_planes.shape
    pw, k2, n = w_planes.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"
    assert a_scale.shape == (m, 1), f"a_scale shape {a_scale.shape}"
    assert w_scale.shape == (1, n), f"w_scale shape {w_scale.shape}"

    bm, bn, bk, kp, np_ = issued_tiles(m, k, n, pa, pw, want_rowsum,
                                       bm=bm, bn=bn, bk=bk)
    a_planes, w_planes = _pad_planes(a_planes, w_planes, kp, np_)
    if np_ > n:
        w_scale = jnp.pad(w_scale, ((0, 0), (0, np_ - n)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, np_ - n)))
    n_k = kp // bk
    has_bias = bias is not None

    # scale vectors padded (with zeros) to full register tiles, which
    # compiled Mosaic needs; the epilogue reads only lane 0 / sublane 0,
    # so values are unchanged
    a_scale = jnp.pad(a_scale, ((0, 0), (0, LANE - 1)))
    w_scale = jnp.pad(w_scale, ((0, SUBLANE - 1), (0, 0)))
    if has_bias:
        bias = jnp.pad(bias, ((0, SUBLANE - 1), (0, 0)))
    as_spec = pl.BlockSpec((bm, LANE), lambda i, j, s: (i, 0))
    ws_spec = pl.BlockSpec((SUBLANE, bn), lambda i, j, s: (0, j))

    in_specs = [
        pl.BlockSpec((pa, bm, bk), lambda i, j, s: (0, i, s)),
        pl.BlockSpec((pw, bk, bn), lambda i, j, s: (0, s, j)),
        as_spec,
        ws_spec,
    ]
    inputs = [a_planes, w_planes, a_scale, w_scale]
    if has_bias:
        in_specs.append(ws_spec)
        inputs.append(bias)

    out_specs = pl.BlockSpec((bm, bn), lambda i, j, s: (i, j))
    out_shape = jax.ShapeDtypeStruct((m, np_), jnp.float32)
    if want_rowsum:
        # one private (bm, LANE) partial per (i, j) tile; the j-block
        # fold happens below in plain jnp (a handful of int32 columns)
        out_specs = (out_specs,
                     pl.BlockSpec((bm, LANE), lambda i, j, s: (i, j)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((m, (np_ // bn) * LANE),
                                          jnp.int32))

    out = pl.pallas_call(
        functools.partial(_pim_matmul_fused_kernel, n_k=n_k, pa=pa, pw=pw,
                          has_bias=has_bias, want_rowsum=want_rowsum),
        grid=(pl.cdiv(m, bm), np_ // bn, n_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="pim_matmul_fused",
    )(*inputs)
    if want_rowsum:
        out, partials = out
        # lane 0 of each j block carries that tile's partial; int32
        # wraparound addition is associative, so this fold is bit-equal
        # to the in-kernel accumulation order
        rowsum = partials.reshape(m, np_ // bn, LANE)[:, :, 0].sum(
            axis=1, dtype=jnp.int32)
        return out[:, :n], rowsum
    return out[:, :n]
