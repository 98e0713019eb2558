"""jit'd public wrapper for the fused analog-readout kernel.

``analog_matmul_fused`` is the planned-weight entry point behind the
engine's ``analog-pallas`` substrate: the auto-ranging pass derives the
per-plane-pair ADC full scale, the readout pass digitizes and reduces in
VMEM — at no point does a (planes, chunks, M, N) intermediate touch HBM.
Model code should not call this directly — program a plan with
``engine.program(w, cfg)`` (``cfg.substrate="analog-pallas"``) and
execute with ``engine.matmul`` so the route stays substrate-keyed.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.analog_readout.analog_readout import (
    DEFAULT_BK, DEFAULT_BM, DEFAULT_BN, analog_fullscale_pallas,
    analog_readout_pallas)
from repro.kernels.analog_readout.ref import (analog_fullscale_ref,
                                              analog_readout_fused_ref,
                                              clamp_fullscale,
                                              inv_half_levels)
from repro.kernels.runtime import resolve_interpret


@functools.partial(jax.jit,
                   static_argnames=("chunk", "adc_bits", "sigma", "bm",
                                    "bn", "bk", "interpret",
                                    "use_ref"))
def analog_matmul_fused(a_planes: jax.Array, w_planes: jax.Array,
                        a_scale: jax.Array, w_scale: jax.Array,
                        seed: Optional[jax.Array] = None,
                        bias: Optional[jax.Array] = None,
                        *, chunk: int, adc_bits: int, sigma: float = 0.0,
                        bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                        bk: int = DEFAULT_BK,
                        interpret: Optional[bool] = None,
                        use_ref: bool = False) -> jax.Array:
    """Nibble planes + scales -> (M, N) float32 through the full analog
    readout chain (chunked PD sums, optional transmission noise, ADC,
    digital accumulation, shift-and-add, dequant epilogue).

    a_planes: (Pa, M, K) int8; w_planes: (Pw, K, N) int8; a_scale: (M, 1)
    per-row act scales; w_scale: (1, N) per-col weight scales; bias:
    optional (1, N). ``seed`` is an int32 scalar feeding the threaded
    per-tile noise key (``None`` or ``sigma=0`` -> the deterministic
    ADC-only transfer, bit-identical to ``ref.analog_readout_fused_ref``
    with ``rng=None``). ``use_ref`` routes to the whole-array jnp oracle
    (noise then drawn from ``PRNGKey(seed)`` — statistically, not
    bitwise, equivalent to the tiled draw).
    """
    pa, m, k = a_planes.shape
    pw, k2, n = w_planes.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"
    has_noise = sigma > 0.0 and seed is not None
    if use_ref:
        rng = jax.random.PRNGKey(seed) if has_noise else None
        return analog_readout_fused_ref(
            a_planes, w_planes, a_scale, w_scale, chunk, adc_bits,
            sigma=sigma if has_noise else 0.0, rng=rng, bias=bias)
    # chunk-align K once here (absolute chunk boundaries make right
    # zero-padding exact); planned weights arrive pre-aligned, so this is
    # a no-op on the engine path
    pad_c = (-k) % chunk
    if pad_c:
        a_planes = jnp.pad(a_planes, ((0, 0), (0, 0), (0, pad_c)))
        w_planes = jnp.pad(w_planes, ((0, 0), (0, pad_c), (0, 0)))
    kw = dict(chunk=chunk, sigma=sigma if has_noise else 0.0, bm=bm,
              bn=bn, bk=bk, interpret=resolve_interpret(interpret))
    fs = analog_fullscale_pallas(a_planes, w_planes, seed, **kw)
    lsb = clamp_fullscale(fs) * inv_half_levels(adc_bits)
    return analog_readout_pallas(a_planes, w_planes, a_scale, w_scale,
                                 lsb, seed, bias, **kw)


__all__ = ["analog_matmul_fused", "analog_fullscale_pallas",
           "analog_readout_pallas", "analog_fullscale_ref",
           "analog_readout_fused_ref"]
