"""Pallas TPU flash attention (forward): VMEM-resident online softmax.

Motivation (EXPERIMENTS.md §Perf iter 3): the pure-JAX blockwise attention
materializes every (s × block) logits tile in HBM — ~1.3 TB/chip for the
granite-20b prefill_32k cell, ~45% of its memory-roofline term. This
kernel keeps logits, the running max/denominator and the output
accumulator in VMEM scratch; HBM sees only q/k/v reads and one output
write.

Grid: (b·kv, q_blocks, k_blocks); the k axis is sequential (carries the
online-softmax state). GQA is handled by folding ``rep`` q-heads per kv
head into the q tile (rows = rep·bq). Causal/window masking is computed
from iota inside the kernel, and whole k-blocks past the causal frontier
are skipped with pl.when.

VMEM per step (bq=512, bk=512, rep<=8, d<=256, f32):
  q tile rep·bq·d ≈ 4 MB, k/v tiles bk·d ≈ 0.5 MB,
  logits rep·bq·bk ≈ 8 MB, acc rep·bq·d ≈ 4 MB — fits v5e VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  n_kblocks: int, bq: int, bk: int, causal: bool,
                  window: int, prefix_len: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk

    def _step():
        q = q_ref[0].astype(jnp.float32) * scale      # (rep, bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)              # (bk, d)
        logits = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (rep, bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = (qpos >= kpos) if causal else jnp.ones((bq, bk), bool)
        ok |= kpos < prefix_len
        if window > 0:
            ok &= ((qpos - kpos) < window) | (kpos < prefix_len)
        logits = jnp.where(ok[None], logits, NEG_INF)

        m_prev = m_ref[...]                            # (rep, bq)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[..., None])         # (rep, bq, bk)
        acc_ref[...] = (acc_ref[...] * alpha[..., None] +
                        jax.lax.dot_general(
                            p, v, (((2,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        l_ref[...] = l_prev * alpha + p.sum(axis=-1)
        m_ref[...] = m_new

    if causal:
        # skip k-blocks entirely above the causal diagonal (they can only
        # contribute through the prefix-LM region, if any)
        run = k_start <= q_start + bq - 1
        if prefix_len > 0:
            run |= k_start < prefix_len
        pl.when(run)(_step)
    else:
        _step()

    @pl.when(ki == n_kblocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-37)[..., None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "prefix_len", "bq", "bk",
                                             "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, window: int = 0,
                           prefix_len: int = 0, bq: int = 512,
                           bk: int = 512, interpret: bool = False
                           ) -> jax.Array:
    """q: (b, s, h, d), k/v: (b, s, kv, d) -> (b, s, h, d)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    n_q, n_k = s // bq, s // bk

    # layout: (b*kv, rep, s, d) for q; (b*kv, s, d) for k/v
    qz = jnp.moveaxis(q.reshape(b, s, kv, rep, d), 1, 3)  # (b,kv,rep,s,d)
    qz = qz.reshape(b * kv, rep, s, d)
    kz = jnp.moveaxis(k, 1, 2).reshape(b * kv, s, d)
    vz = jnp.moveaxis(v, 1, 2).reshape(b * kv, s, d)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, n_kblocks=n_k, bq=bq, bk=bk,
                          causal=causal, window=window,
                          prefix_len=prefix_len, scale=1.0 / math.sqrt(d)),
        grid=(b * kv, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, rep, bq, d), lambda z, i, j: (z, 0, i, 0)),
            pl.BlockSpec((1, bk, d), lambda z, i, j: (z, j, 0)),
            pl.BlockSpec((1, bk, d), lambda z, i, j: (z, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, rep, bq, d), lambda z, i, j: (z, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kv, rep, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, bq, d), jnp.float32),   # output accumulator
            pltpu.VMEM((rep, bq), jnp.float32),      # running max
            pltpu.VMEM((rep, bq), jnp.float32),      # running denominator
        ],
        interpret=interpret,
        name="flash_attention",
    )(qz, kz, vz)

    out = out.reshape(b, kv, rep, s, d)
    return jnp.moveaxis(out, 3, 1).reshape(b, s, h, d)
