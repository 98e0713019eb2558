"""Pallas TPU kernel: fused OPIMA analog-readout matmul.

The jnp ``analog`` substrate materializes the full (Pa, Pw, KC, M, N)
chunk-sum tensor in HBM before quantizing — the physically-faithful mode
was the slowest route through the engine. This kernel runs the whole
readout chain (per-WDM-chunk photodetector sums -> optional transmission
noise -> shared auto-ranged ADC -> integer code accumulation ->
shift-and-add recombination -> dequant epilogue) on (bm, bn, bk) VMEM
tiles: no chunk-sum intermediate ever touches HBM.

Two passes over the operands (the classic streaming-quantizer shape):

  * ``analog_fullscale_pallas`` — the auto-ranging pass. The shared ADC
    full scale is ``max |chunk sum|`` over the *whole* (pairs, KC, M, N)
    extent — a global reduction — so it cannot be fused into a single
    tiled pass. This kernel recomputes chunk sums per tile and
    max-accumulates into one (SUBLANE, LANE) output block; its output is
    one scalar, not an (M, N, planes, chunks) tensor.
  * ``analog_readout_pallas`` — the readout pass. Per tile and plane
    pair: chunk sums, noise, ADC codes (``round(s / lsb)`` as int32),
    shift-weighted code accumulation over the sequential K grid axis
    into an int32 VMEM scratch (exact integer arithmetic, so neither
    K-tile order nor XLA fast-math reassociation can perturb it), and on
    the last K step the fused epilogue: one ``lsb`` rescale of the int32
    accumulator, then ``(acc * a_scale) * w_scale (+ bias)`` — the same
    op order as :mod:`.ref`, bit-for-bit on the deterministic path.

Noise (``sigma > 0``) uses a *threaded key*: a host-derived int32 seed
arrives in SMEM and each grid step folds its ``program_id`` triple into a
``jax.random`` key, so the two passes draw identical per-tile normals
(the auto-range must see the same noise the converter digitizes) while
staying reproducible and vmap-safe (expert stacks batch the seed).
``pltpu.prng_seed`` would be the on-device alternative, but it has no
interpret-mode lowering on CPU, and bit-agreement *between the two
passes* is the hard requirement here. The noise path runs in interpret
mode only: compiled for a TPU, the per-tile (pairs, KC, bm, bn) draw
overflows VMEM at prefill tiles, so a compiled noise call raises
:class:`NotImplementedError` (see :func:`_check_noise_lowerable`).

Scale/bias vectors reuse the lane-padded (SUBLANE/LANE) register-tile
layout of the exact kernel so compiled Mosaic lowering never sees a
width-1 minor axis.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pim_matmul.pim_matmul import LANE, SUBLANE

DEFAULT_BM = 128
DEFAULT_BN = 256
# Default tiles are tuned for the interpret path (an XLA while-loop over
# grid steps, where step count dominates wall clock): (128, 256, 512)
# minimizes steps across decode- and prefill-shaped problems.
DEFAULT_BK = 512


def analog_tiles(m: int, k: int, n: int, chunk: int,
                 bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                 bk: int = DEFAULT_BK) -> Tuple[int, int, int]:
    """Deterministic (bm, bn, bk) tile selection; ``bk`` is always a
    multiple of ``chunk`` so tile edges land on WDM-chunk boundaries
    (chunk boundaries are absolute — see :mod:`.ref`). ``k`` must already
    be a chunk multiple."""
    assert k % chunk == 0, f"k={k} not chunk-aligned (chunk={chunk})"
    bm, bn = min(bm, m), min(bn, n)
    bk = min(max(chunk, (bk // chunk) * chunk), k)
    return bm, bn, bk


def _check_noise_lowerable(has_noise: bool, interpret: bool) -> None:
    """The transmission-noise path has no compiled (Mosaic) form yet: its
    per-tile normal draw is (pairs, KC, bm, bn) f32 — 8 MiB per plane
    pair at the default tiles — which exhausts VMEM at prefill shapes.
    Refuse it loudly rather than fall back to the interpreter."""
    if has_noise and not interpret:
        raise NotImplementedError(
            "analog-pallas transmission-noise path (sigma > 0 with a seed) "
            "has no compiled TPU lowering; run the noise study with "
            "interpret=True or on the 'analog' jnp substrate")


def _tile_noise(seed, npairs: int, kc: int, bm: int, bn: int) -> jax.Array:
    """Per-tile standard normals from a threaded key: the (i, j, s) grid
    position folds into the seed, so the full-scale and readout passes —
    which share a grid — draw bit-identical noise for every tile."""
    key = jax.random.PRNGKey(seed)
    for axis in range(3):
        key = jax.random.fold_in(key, pl.program_id(axis))
    return jax.random.normal(key, (npairs, kc, bm, bn), jnp.float32)


def _chunk_sum(a_ref, w_ref, d: int, e: int, c: int, *, chunk: int,
               sigma: float, noise) -> jax.Array:
    """Noisy photodetector sum of WDM chunk ``c`` for one (act-plane,
    weight-plane) pair of one (bm, bk) x (bk, bn) tile. Returns (bm, bn)
    float32 — an exact small integer plus (optionally) the
    transmission-noise term. Shared by both kernels so the auto-range
    pass sees exactly the signal the readout pass digitizes.

    ``c`` is a Python int: the chunk's columns are a static slice of the
    tile (Mosaic lowers no in-kernel dynamic_slice), and its sum is one
    int8 x int8 -> int32 matmul of contraction ``chunk`` — exact, since
    nibble products summed over a chunk stay far inside int32."""
    cols = slice(c * chunk, (c + 1) * chunk)
    a_c = a_ref[d, :, cols]                       # (bm, chunk) int8
    w_c = w_ref[e, cols, :]                       # (chunk, bn) int8
    dims = (((1,), (0,)), ((), ()))
    sums = jax.lax.dot_general(a_c, w_c, dims,
                               preferred_element_type=jnp.int32)
    sums = sums.astype(jnp.float32)
    if sigma > 0.0:
        a_f, w_f = a_c.astype(jnp.float32), w_c.astype(jnp.float32)
        prod_sq = jax.lax.dot_general(a_f * a_f, w_f * w_f, dims,
                                      preferred_element_type=jnp.float32)
        sums = sums + sigma * jnp.sqrt(prod_sq) * noise[c]
    return sums


def _fullscale_kernel(*refs, chunk: int, kc: int, pa: int, pw: int,
                      sigma: float, has_noise: bool):
    """Auto-ranging pass: running max |chunk sum| over every plane pair
    and grid step, accumulated into one (SUBLANE, LANE) block (the scalar
    is broadcast across the block so no width-1 writes are needed). Max
    is associative, so the per-chunk fold is bit-identical to a
    whole-tile reduction."""
    if has_noise:
        a_ref, w_ref, seed_ref, o_ref = refs
    else:
        a_ref, w_ref, o_ref = refs
    first = ((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
             & (pl.program_id(2) == 0))

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)   # |chunk sums| >= 0

    noise = (_tile_noise(seed_ref[0], pa * pw, kc,
                         a_ref.shape[1], w_ref.shape[2])
             if has_noise else None)
    tile_max = jnp.float32(0.0)
    for d in range(pa):
        for e in range(pw):
            pair_noise = noise[d * pw + e] if has_noise else None
            for c in range(kc):
                sums = _chunk_sum(a_ref, w_ref, d, e, c, chunk=chunk,
                                  sigma=sigma, noise=pair_noise)
                tile_max = jnp.maximum(tile_max, jnp.max(jnp.abs(sums)))
    o_ref[...] = jnp.maximum(o_ref[...],
                             jnp.full(o_ref.shape, tile_max))


def _readout_kernel(*refs, chunk: int, kc: int, pa: int, pw: int,
                    sigma: float, has_noise: bool, has_bias: bool,
                    n_k: int):
    """Readout pass: shift-weighted ADC codes accumulated in int32 across
    the sequential K axis; fused rescale/dequant epilogue on the last K
    step.

    Ref order: a, w, a_scale, w_scale, lsb(SMEM) [, seed(SMEM)] [, bias],
    out, int32 acc scratch (bm, bn).
    """
    a_ref, w_ref, as_ref, ws_ref, lsb_ref = refs[:5]
    rest = refs[5:]
    if has_noise:
        seed_ref, rest = rest[0], rest[1:]
    if has_bias:
        b_ref, rest = rest[0], rest[1:]
    o_ref, acc_ref = rest
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    noise = (_tile_noise(seed_ref[0], pa * pw, kc,
                         a_ref.shape[1], w_ref.shape[2])
             if has_noise else None)
    lsb = lsb_ref[0]
    acc = acc_ref[...]
    for d in range(pa):
        for e in range(pw):
            pair_noise = noise[d * pw + e] if has_noise else None
            pair_codes = jnp.zeros(acc.shape, jnp.int32)
            for c in range(kc):
                sums = _chunk_sum(a_ref, w_ref, d, e, c, chunk=chunk,
                                  sigma=sigma, noise=pair_noise)
                # shared auto-ranged ADC: |sums| <= full_scale by
                # construction so codes are in [-half_levels,
                # half_levels] — no clamp; the digital accumulator sums
                # shift-weighted codes in int32 (exact — neither K-tile
                # order nor fast-math can perturb it)
                pair_codes = pair_codes + jnp.round(sums / lsb).astype(
                    jnp.int32)
            acc = acc + pair_codes * (16 ** (d + e))
    acc_ref[...] = acc

    @pl.when(k_step == n_k - 1)
    def _write_out():
        # one lsb rescale of the integer accumulator (the TIA/ADC
        # calibration applied once), then (acc * a_s) * w_s (+ b) — the
        # exact op order of the oracle, for bit-identical dequantization.
        out = acc_ref[...].astype(jnp.float32) * lsb_ref[0]
        a_s = as_ref[...][:, :1]          # (bm, 1): value lives in lane 0
        w_s = ws_ref[...][:1, :]          # (1, bn): value lives in row 0
        out = out * a_s * w_s
        if has_bias:
            out = out + b_ref[...][:1, :]
        o_ref[...] = out


def _pad_operands(a_planes, w_planes, a_scale, w_scale, bias, bm, bn, bk):
    """Zero-pad everything to tile multiples (exact for this datapath:
    padded products are 0, padded chunk sums are 0, their codes are 0,
    and max-accumulation ignores zeros)."""
    pa, m, k = a_planes.shape
    pw, _, n = w_planes.shape
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
    return a_planes, w_planes, a_scale, w_scale, bias


@functools.partial(jax.jit,
                   static_argnames=("chunk", "sigma", "bm", "bn", "bk",
                                    "interpret"))
def analog_fullscale_pallas(a_planes: jax.Array, w_planes: jax.Array,
                            seed: Optional[jax.Array] = None,
                            *, chunk: int, sigma: float = 0.0,
                            bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                            bk: int = DEFAULT_BK,
                            interpret: bool = False) -> jax.Array:
    """Auto-ranging pass: the shared ADC full scale.

    Args:
      a_planes: (Pa, M, K) int8 activation nibble planes, K chunk-aligned.
      w_planes: (Pw, K, N) int8 weight nibble planes.
      seed: int32 scalar for the threaded noise key (None -> no noise).
      chunk: WDM chunk length (products summed optically per chunk).
      sigma: relative transmission-noise sigma (0 -> deterministic).

    Returns:
      float32 scalar — the unclamped full scale, bit-identical to
      ``ref.analog_fullscale_ref`` on the deterministic path.
    """
    pa, m, k = a_planes.shape
    pw, k2, n = w_planes.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"
    has_noise = sigma > 0.0 and seed is not None
    _check_noise_lowerable(has_noise, interpret)
    bm, bn, bk = analog_tiles(m, k, n, chunk, bm, bn, bk)
    a_planes, w_planes, _, _, _ = _pad_operands(
        a_planes, w_planes, jnp.zeros((m, 1), jnp.float32),
        jnp.zeros((1, n), jnp.float32), None, bm, bn, bk)
    mp, kp, np_ = a_planes.shape[1], a_planes.shape[2], w_planes.shape[2]
    n_k = kp // bk

    in_specs = [
        pl.BlockSpec((pa, bm, bk), lambda i, j, s: (0, i, s)),
        pl.BlockSpec((pw, bk, bn), lambda i, j, s: (0, s, j)),
    ]
    inputs = [a_planes, w_planes]
    if has_noise:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(jnp.asarray(seed, jnp.int32).reshape((1,)))

    kc = bk // chunk
    out = pl.pallas_call(
        functools.partial(_fullscale_kernel, chunk=chunk, kc=kc, pa=pa,
                          pw=pw, sigma=sigma if has_noise else 0.0,
                          has_noise=has_noise),
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=in_specs,
        # every grid step max-accumulates into the same block
        out_specs=pl.BlockSpec((SUBLANE, LANE), lambda i, j, s: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANE, LANE), jnp.float32),
        interpret=interpret,
    )(*inputs)
    return out[0, 0]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "sigma", "bm", "bn", "bk",
                                    "interpret"))
def analog_readout_pallas(a_planes: jax.Array, w_planes: jax.Array,
                          a_scale: jax.Array, w_scale: jax.Array,
                          lsb: jax.Array,
                          seed: Optional[jax.Array] = None,
                          bias: Optional[jax.Array] = None,
                          *, chunk: int, sigma: float = 0.0,
                          bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                          bk: int = DEFAULT_BK,
                          interpret: bool = False) -> jax.Array:
    """Readout pass: fused chunk sums -> noise -> ADC -> integer code
    accumulation -> shift-and-add -> dequant epilogue.

    Args:
      a_planes: (Pa, M, K) int8 activation nibble planes, K chunk-aligned.
      w_planes: (Pw, K, N) int8 weight nibble planes.
      a_scale: (M, 1) f32 per-row dynamic activation scales.
      w_scale: (1, N) f32 per-column weight scales.
      lsb: f32 scalar — the shared ADC step (from the full-scale pass).
      seed: int32 scalar threaded noise key (must match the one given to
        the full-scale pass so the converter digitizes the ranged signal).
      bias: optional (1, N) f32, added after dequantization.

    Returns:
      (M, N) float32 — bit-identical to ``ref.analog_readout_fused_ref``
      with ``rng=None`` (the converter's deterministic transfer).
    """
    pa, m, k = a_planes.shape
    pw, k2, n = w_planes.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"
    assert a_scale.shape == (m, 1), f"a_scale shape {a_scale.shape}"
    assert w_scale.shape == (1, n), f"w_scale shape {w_scale.shape}"
    has_noise = sigma > 0.0 and seed is not None
    _check_noise_lowerable(has_noise, interpret)
    has_bias = bias is not None
    bm, bn, bk = analog_tiles(m, k, n, chunk, bm, bn, bk)
    a_planes, w_planes, a_scale, w_scale, bias = _pad_operands(
        a_planes, w_planes, a_scale, w_scale, bias, bm, bn, bk)
    mp, kp, np_ = a_planes.shape[1], a_planes.shape[2], w_planes.shape[2]
    n_k = kp // bk

    # lane-padded register-tile scale layout (see pim_matmul.py)
    a_scale = jnp.pad(a_scale, ((0, 0), (0, LANE - 1)))
    w_scale = jnp.pad(w_scale, ((0, SUBLANE - 1), (0, 0)))
    ws_spec = pl.BlockSpec((SUBLANE, bn), lambda i, j, s: (0, j))
    in_specs = [
        pl.BlockSpec((pa, bm, bk), lambda i, j, s: (0, i, s)),
        pl.BlockSpec((pw, bk, bn), lambda i, j, s: (0, s, j)),
        pl.BlockSpec((bm, LANE), lambda i, j, s: (i, 0)),
        ws_spec,
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    inputs = [a_planes, w_planes, a_scale, w_scale,
              lsb.astype(jnp.float32).reshape((1,))]
    if has_noise:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(jnp.asarray(seed, jnp.int32).reshape((1,)))
    if has_bias:
        in_specs.append(ws_spec)
        inputs.append(jnp.pad(bias, ((0, SUBLANE - 1), (0, 0))))

    kc = bk // chunk
    out = pl.pallas_call(
        functools.partial(_readout_kernel, chunk=chunk, kc=kc, pa=pa,
                          pw=pw, sigma=sigma if has_noise else 0.0,
                          has_noise=has_noise, has_bias=has_bias, n_k=n_k),
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        # shift-weighted ADC-code accumulator, persistent across the K axis
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(*inputs)
    return out[:m, :n]
