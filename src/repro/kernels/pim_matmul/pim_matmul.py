"""Pallas TPU kernel: OPIMA bit-sliced (nibble-plane) integer matmul.

This is the paper's PIM datapath adapted to the TPU memory hierarchy
(DESIGN.md §2): weight nibbles live in VMEM tiles (the "subarray"), each
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

Tiling:
  grid = (M/bm, N/bn, K/bk); K is the innermost (sequential) axis so each
  (m, n) output tile accumulates across K steps in a VMEM scratch
  accumulator, written out on the last K step. Plane pairs are unrolled
  inside the kernel body (Pa, Pw <= 2 in practice: 4b/8b operands).
  ``kernel_tiles`` is the deterministic tile chooser shared with the
  engine's :class:`~repro.core.pim.PlannedWeights` pre-padding: weight
  planes padded at programming time always land on the same tile grid the
  kernel would pick, so the per-call padding is a no-op.

VMEM budget per step (bm=bn=128, bk=512, Pa=Pw=2):
  a tile 2*128*512 B + w tile 2*512*128 B + acc 128*128*4 B ~= 0.33 MiB,
  comfortably inside the ~16 MiB VMEM of a TPU v5e core, leaving room for
  double-buffered prefetch of the next K tiles.

dot dims are (128, 512) x (512, 128) — MXU-aligned (multiples of 128).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512

# Mosaic register-tile geometry for float32 operands: the fused epilogue's
# scale vectors are padded to full (sublane, lane) tiles so compiled
# lowering never sees a width-1 minor axis (interpret mode accepts those;
# real-TPU Mosaic wants lane-aligned operands).
LANE = 128
SUBLANE = 8


def kernel_tiles(m: int, k: int, n: int, bm: int = DEFAULT_BM,
                 bn: int = DEFAULT_BN, bk: int = DEFAULT_BK
                 ) -> Tuple[int, int, int]:
    """Deterministic (bm, bn, bk) tile selection for problem (M, K, N).

    Shared between the kernel wrappers and ``prepare_weights`` so that
    planes padded once at weight-programming time stay valid for every
    subsequent call: for any K' that is a multiple of ``ceil(k/bk)*bk``
    the recomputed tile divides it exactly.
    """
    return min(bm, m), min(bn, n), min(bk, k)


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
                      bm: int = 128, bn: int = 128, bk: int = 512,
                      interpret: bool = False) -> jax.Array:
    """Bit-sliced integer matmul via pallas_call.

    Args:
      a_planes: (Pa, M, K) int8 nibble planes of the activations.
      w_planes: (Pw, K, N) int8 nibble planes of the weights.
      bm/bn/bk: VMEM tile sizes (MXU-aligned).
      interpret: run in interpreter mode (CPU validation).

    Returns:
      (M, N) int32 — bit-exact vs. ref.pim_matmul_ref.
    """
    pa, m, k = a_planes.shape
    pw, k2, n = w_planes.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"

    bm, bn, bk = kernel_tiles(m, k, n, bm, bn, bk)
    # pad to tile multiples (zero padding is exact for integer matmul)
    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-k) % bk
    if pad_m or pad_k:
        a_planes = jnp.pad(a_planes, ((0, 0), (0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        w_planes = jnp.pad(w_planes, ((0, 0), (0, pad_k), (0, pad_n)))
    mp, kp, np_ = m + pad_m, k + pad_k, n + pad_n
    n_k = kp // bk

    out = pl.pallas_call(
        functools.partial(_pim_matmul_kernel, n_k=n_k, pa=pa, pw=pw),
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=[
            pl.BlockSpec((pa, bm, bk), lambda i, j, s: (0, i, s)),
            pl.BlockSpec((pw, bk, bn), lambda i, j, s: (0, s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        # int32 accumulator tile, persistent across the sequential K axis
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(a_planes, w_planes)
    return out[:m, :n]


def _pim_matmul_fused_kernel(a_ref, w_ref, as_ref, ws_ref, *rest, n_k: int,
                             pa: int, pw: int, has_bias: bool,
                             lane_pad: bool, want_rowsum: bool):
    """One (m, n, k) grid step with the fused dequant epilogue.

    a_ref: (Pa, bm, bk) int8  — activation nibble planes tile
    w_ref: (Pw, bk, bn) int8  — weight nibble planes tile
    as_ref: (bm, LANE) f32    — per-row activation scales, value in lane 0
                                ((bm, 1) when lane_pad=False)
    ws_ref: (SUBLANE, bn) f32 — per-column weight scales, value in row 0
                                ((1, bn) when lane_pad=False)
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

    ``lane_pad`` selects the register-tile-aligned scale layout; only the
    slice read in the epilogue differs — arithmetic is identical, and the
    parity test pins the two layouts bit-for-bit against each other.
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
        if lane_pad:
            a_s = as_ref[...][:, :1]        # (bm, 1): value lives in lane 0
            w_s = ws_ref[...][:1, :]        # (1, bn): value lives in row 0
        else:
            a_s = as_ref[...]
            w_s = ws_ref[...]
        out = acc_ref[...].astype(jnp.float32) * a_s * w_s
        if has_bias:
            out = out + (b_ref[...][:1, :] if lane_pad else b_ref[...])
        o_ref[...] = out


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret",
                                    "lane_pad", "want_rowsum"))
def pim_matmul_fused_pallas(a_planes: jax.Array, w_planes: jax.Array,
                            a_scale: jax.Array, w_scale: jax.Array,
                            bias: Optional[jax.Array] = None,
                            bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                            bk: int = DEFAULT_BK,
                            interpret: bool = False,
                            lane_pad: bool = True,
                            want_rowsum: bool = False):
    """Bit-sliced integer matmul with the fused dequantization epilogue.

    Args:
      a_planes: (Pa, M, K) int8 nibble planes of the activations.
      w_planes: (Pw, K, N) int8 nibble planes of the weights.
      a_scale: (M, 1) f32 per-row dynamic activation scales.
      w_scale: (1, N) f32 per-column weight scales.
      bias: optional (1, N) f32, added after dequantization.
      bm/bn/bk: VMEM tile sizes (MXU-aligned).
      interpret: run in interpreter mode (CPU validation).
      lane_pad: pad the width-1 scale vectors to full (SUBLANE, LANE)
        register tiles so compiled Mosaic lowering is clean (default).
        ``False`` keeps the legacy width-1 BlockSpecs — interpret-mode
        only, retained as the parity baseline for tests.
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

    bm, bn, bk = kernel_tiles(m, k, n, bm, bn, bk)
    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-k) % bk
    if pad_m or pad_k:
        a_planes = jnp.pad(a_planes, ((0, 0), (0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        w_planes = jnp.pad(w_planes, ((0, 0), (0, pad_k), (0, pad_n)))
    if pad_m:
        a_scale = jnp.pad(a_scale, ((0, pad_m), (0, 0)))
    if pad_n:
        w_scale = jnp.pad(w_scale, ((0, 0), (0, pad_n)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, pad_n)))
    mp, kp, np_ = m + pad_m, k + pad_k, n + pad_n
    n_k = kp // bk
    has_bias = bias is not None

    if lane_pad:
        # scale vectors padded (with zeros) to full register tiles; the
        # epilogue reads only lane 0 / sublane 0, so values are unchanged
        a_scale = jnp.pad(a_scale, ((0, 0), (0, LANE - 1)))
        w_scale = jnp.pad(w_scale, ((0, SUBLANE - 1), (0, 0)))
        if has_bias:
            bias = jnp.pad(bias, ((0, SUBLANE - 1), (0, 0)))
        as_spec = pl.BlockSpec((bm, LANE), lambda i, j, s: (i, 0))
        ws_spec = pl.BlockSpec((SUBLANE, bn), lambda i, j, s: (0, j))
    else:
        # legacy width-1 scale specs, kept for interpret-mode parity
        # tests; compiled Mosaic uses the lane_pad branch above
        # repro-lint: disable=RPR401
        as_spec = pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0))
        ws_spec = pl.BlockSpec((1, bn), lambda i, j, s: (0, j))

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
    out_shape = jax.ShapeDtypeStruct((mp, np_), jnp.float32)
    if want_rowsum:
        # one private (bm, LANE) partial per (i, j) tile; the j-block
        # fold happens below in plain jnp (a handful of int32 columns)
        out_specs = (out_specs,
                     pl.BlockSpec((bm, LANE), lambda i, j, s: (i, j)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((mp, (np_ // bn) * LANE),
                                          jnp.int32))

    out = pl.pallas_call(
        functools.partial(_pim_matmul_fused_kernel, n_k=n_k, pa=pa, pw=pw,
                          has_bias=has_bias, lane_pad=lane_pad,
                          want_rowsum=want_rowsum),
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(*inputs)
    if want_rowsum:
        out, partials = out
        # lane 0 of each j block carries that tile's partial; int32
        # wraparound addition is associative, so this fold is bit-equal
        # to the in-kernel accumulation order
        rowsum = partials.reshape(mp, np_ // bn, LANE)[:m, :, 0].sum(
            axis=1, dtype=jnp.int32)
        return out[:m, :n], rowsum
    return out[:m, :n]
