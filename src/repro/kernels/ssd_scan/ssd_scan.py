"""Pallas TPU kernel: chunked Mamba2 SSD scan.

Grid: (BH, L/Q) with the chunk axis sequential; the (N, P) SSM state lives
in a VMEM scratch accumulator carried across chunk steps. Per chunk the
kernel does three MXU contractions (scores = C·Bᵀ, intra = scores·X,
state update = Bᵀ·X) plus the VPU decay math — the standard SSD duality:
quadratic *inside* the chunk, linear recurrence *across* chunks.

VMEM per step (Q=128, N=128, P=64, f32):
  x (Q,P) 32 KiB + b,c (Q,N) 2x64 KiB + scores (Q,Q) 64 KiB
  + state (N,P) 32 KiB  « VMEM budget; Q could go to 512 on real HW.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, sfin_ref, state_ref,
                *, n_chunks: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0]            # (Q, P)
    a = a_ref[0]            # (Q,)
    b = b_ref[0]            # (Q, N)
    c = c_ref[0]            # (Q, N)
    s = state_ref[...]      # (N, P)

    la = jnp.log(jnp.maximum(a, 1e-37))
    cl = jnp.cumsum(la)                                   # (Q,) inclusive
    # intra-chunk quadratic part
    seg = jnp.exp(cl[:, None] - cl[None, :])
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmat = jnp.where(ii >= jj, seg, 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * lmat
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk contribution from the carried state
    y += jnp.exp(cl)[:, None] * jax.lax.dot_general(
        c, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0] = y

    # state carry: S <- decay(chunk)·S + sum_j decay(j->end) b_j x_jᵀ
    decay_end = jnp.exp(cl[-1] - cl)                      # (Q,)
    bw = b * decay_end[:, None]
    s_new = jnp.exp(cl[-1]) * s + jax.lax.dot_general(
        bw, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    state_ref[...] = s_new

    @pl.when(ci == n_chunks - 1)
    def _write_final():
        sfin_ref[0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
                    chunk: int = 128, interpret: bool = False
                    ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. x: (BH, L, P) f32, a: (BH, L), b/c: (BH, L, N).

    Returns y (BH, L, P), final state (BH, N, P).
    """
    bh, l, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, l)
    assert l % chunk == 0, f"L={l} must be divisible by chunk={chunk}"
    n_chunks = l // chunk

    y, s_fin = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=n_chunks, chunk=chunk),
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda z, ci: (z, ci, 0)),
            pl.BlockSpec((1, chunk), lambda z, ci: (z, ci)),
            pl.BlockSpec((1, chunk, n), lambda z, ci: (z, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda z, ci: (z, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda z, ci: (z, ci, 0)),
            pl.BlockSpec((1, n, p), lambda z, ci: (z, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, l, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x.astype(jnp.float32), a.astype(jnp.float32), b.astype(jnp.float32),
      c.astype(jnp.float32))
    return y, s_fin
