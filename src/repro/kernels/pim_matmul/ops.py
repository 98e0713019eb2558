"""jit'd public wrappers for the PIM matmul kernel.

``pim_matmul_fused`` is the planned-weight entry point behind the engine's
``exact-pallas`` substrate (int32 accumulation + in-kernel dequant
epilogue; see :mod:`repro.engine.substrates`); ``pim_matmul_int`` is the
raw integer-plane entry point the mesh row split sums across devices.
Model code should not call these directly — program a plan with
``engine.program`` and execute with ``engine.matmul`` so the route stays
substrate-keyed.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.pim_matmul.pim_matmul import (pim_matmul_fused_pallas,
                                                 pim_matmul_pallas)
from repro.kernels.pim_matmul.ref import pim_matmul_fused_ref, pim_matmul_ref
from repro.kernels.runtime import resolve_interpret


def pim_matmul_int(a_planes: jax.Array, w_planes: jax.Array,
                   interpret: Optional[bool] = None, use_ref: bool = False
                   ) -> jax.Array:
    """(Pa, M, K) x (Pw, K, N) nibble planes -> (M, N) int32."""
    if use_ref:
        return pim_matmul_ref(a_planes, w_planes)
    return pim_matmul_pallas(a_planes, w_planes,
                             interpret=resolve_interpret(interpret))


def pim_matmul_fused(a_planes: jax.Array, w_planes: jax.Array,
                     a_scale: jax.Array, w_scale: jax.Array,
                     bias: Optional[jax.Array] = None,
                     interpret: Optional[bool] = None, use_ref: bool = False,
                     want_rowsum: bool = False):
    """Nibble planes + scales -> (M, N) float32 via the fused epilogue.

    a_scale: (M, 1) per-row act scales; w_scale: (1, N) per-col weight
    scales; bias: optional (1, N). Bit-identical to pim_matmul_fused_ref.
    ``want_rowsum`` also returns the (M,) int32 accumulator row-sums for
    ABFT checksum verification (``(out, rowsum)`` pair).
    """
    if use_ref:
        return pim_matmul_fused_ref(a_planes, w_planes, a_scale, w_scale,
                                    bias, want_rowsum=want_rowsum)
    return pim_matmul_fused_pallas(a_planes, w_planes, a_scale, w_scale,
                                   bias,
                                   interpret=resolve_interpret(interpret),
                                   want_rowsum=want_rowsum)
