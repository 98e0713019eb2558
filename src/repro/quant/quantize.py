"""Quantization substrate for OPIMA.

Symmetric per-channel / per-tensor integer quantization used to place model
parameters into OPCM multi-level cells (4 bits/cell) and to encode activations
onto laser amplitudes. Pure JAX; differentiable via straight-through estimators
so QAT works through the same code path.

Conventions
-----------
* ``bits`` counts *signed* integer bits: int8 -> [-127, 127], int4 -> [-7, 7].
  We use a symmetric range (no -128/-8) so that negation is exact, matching the
  paper's sign-magnitude optical encoding (amplitude = magnitude, sign handled
  digitally in the aggregation unit).
* ``axis`` selects per-channel scales (reduction over all other axes).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp


def qmax(bits: int) -> int:
    """Largest representable magnitude for a signed symmetric ``bits`` code."""
    return (1 << (bits - 1)) - 1


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """A quantized tensor: integer codes + float scale.

    ``values`` are stored as int8 regardless of logical bit width (nibble
    packing is a separate, explicit step — see :mod:`repro.quant.nibbles`).
    """

    values: jax.Array            # int8 codes in [-qmax, qmax]
    scale: jax.Array             # f32, broadcastable to values.shape
    bits: int = 8                # logical bit width of the codes

    def dequantize(self) -> jax.Array:
        return self.values.astype(jnp.float32) * self.scale

    # pytree plumbing -----------------------------------------------------
    def tree_flatten(self):
        return (self.values, self.scale), (self.bits,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, scale = children
        return cls(values=values, scale=scale, bits=aux[0])


def compute_scale(x: jax.Array, bits: int,
                  axis: Optional[Sequence[int]] = None,
                  eps: float = 1e-8) -> jax.Array:
    """abs-max symmetric scale. ``axis=None`` -> per-tensor."""
    amax = jnp.max(jnp.abs(x)) if axis is None else jnp.max(
        jnp.abs(x), axis=tuple(axis), keepdims=True)
    return scale_from_amax(amax, bits, eps)


def scale_from_amax(amax: jax.Array, bits: int, eps: float = 1e-8
                    ) -> jax.Array:
    """The symmetric scale of values whose abs max is ``amax``, for
    callers that reach the abs max another way than a reduction over the
    values (a conv's patch rows, from the unexpanded map)."""
    return jnp.maximum(amax, eps) / qmax(bits)


def quantize(x: jax.Array, bits: int = 8,
             axis: Optional[Sequence[int]] = None,
             scale: Optional[jax.Array] = None) -> QTensor:
    """Symmetric round-to-nearest quantization."""
    if scale is None:
        scale = compute_scale(x, bits, axis)
    q = jnp.clip(jnp.round(x / scale), -qmax(bits), qmax(bits))
    dtype = jnp.int8 if bits <= 8 else jnp.int32
    return QTensor(values=q.astype(dtype), scale=scale.astype(jnp.float32),
                   bits=bits)


def fake_quantize(x: jax.Array, bits: int = 8,
                  axis: Optional[Sequence[int]] = None) -> jax.Array:
    """Quantize-dequantize with a straight-through estimator gradient.

    Forward: dequantize(quantize(x)).  Backward: identity on the clipped
    region (STE), zero outside — the standard QAT primitive.
    """
    scale = compute_scale(x, bits, axis)
    limit = scale * qmax(bits)
    qdq = quantize(x, bits, axis, scale=scale).dequantize()
    # STE: qdq = x + stop_grad(qdq - x), with gradient masked to the
    # representable range.
    inside = (jnp.abs(x) <= limit).astype(x.dtype)
    return x * inside + jax.lax.stop_gradient(qdq - x * inside)


def dynamic_quantize_activations(x: jax.Array, bits: int = 8) -> QTensor:
    """Per-row (token) dynamic activation quantization: scales over the last
    axis are what the MDL array re-tunes per driven vector in OPIMA."""
    axis = (x.ndim - 1,)
    return quantize(x, bits=bits, axis=axis)


@partial(jax.jit, static_argnames=("bits",))
def quantization_mse(x: jax.Array, bits: int) -> jax.Array:
    """Mean-squared quantization error — used by tests & Table-II analysis."""
    return jnp.mean((fake_quantize(x, bits) - x) ** 2)
