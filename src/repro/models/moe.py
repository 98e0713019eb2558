"""Mixture-of-Experts FFN: top-k router + dropless grouped matmul.

Three execution paths:

* ``local``  — sort-by-expert + ``jax.lax.ragged_dot`` over the full expert
  stack. Exact/dropless. Used on a single device and inside the EP shards.
* ``ep``     — expert parallelism over the 'model' mesh axis via shard_map
  (DeepSeek-style reuse of the TP axis): every shard owns E/ep_size experts,
  routes the *local* token batch against its own experts with ragged_dot,
  and a psum over 'model' combines contributions. All ops inside the shard
  are local, so nothing depends on SPMD partitioning of ragged_dot.
* ``pim``    — expert weights programmed into the PIM engine as
  :class:`~repro.core.pim.ExpertStackedPlan` (serving's
  ``plan_params_for_pim``): every token drives past every expert's
  stationary 'OPCM' array and the aggregation applies the router weights —
  the weight-stationary dropless mapping. Selected by the params
  themselves (plans instead of float stacks), not by a flag.

Router follows qwen3-moe: softmax over all experts, top-k, renormalize.
Aux losses: load-balance (Switch-style) + router z-loss, returned to the
caller for the training objective.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_context
from repro.engine import ExpertStackedPlan, matmul as engine_matmul
from repro.models.layers import Params, dense_init


def moe_init(key, d_model: int, num_experts: int, d_ff: int,
             shared_experts: int = 0, shared_d_ff: int = 0,
             dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(d_model)
    p = {
        "router_de": dense_init(ks[0], d_model, num_experts,
                                dtype=jnp.float32),
        "wi_edf": (jax.random.normal(ks[1], (num_experts, d_model, d_ff)) *
                   scale).astype(dtype),
        "wg_edf": (jax.random.normal(ks[2], (num_experts, d_model, d_ff)) *
                   scale).astype(dtype),
        "wo_efd": (jax.random.normal(ks[3], (num_experts, d_ff, d_model)) /
                   jnp.sqrt(d_ff)).astype(dtype),
    }
    if shared_experts > 0:
        sd = shared_d_ff or d_ff
        from repro.models.layers import mlp_init
        p["shared"] = mlp_init(ks[4], d_model, shared_experts * sd,
                               gated=True, dtype=dtype)
    return p


def _route(router_de: jax.Array, x: jax.Array, k: int
           ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """x: (T, D) -> (probs (T,k), ids (T,k), lb_loss, z_loss)."""
    logits = (x.astype(jnp.float32) @ router_de.astype(jnp.float32))
    full = jax.nn.softmax(logits, axis=-1)
    probs, ids = jax.lax.top_k(full, k)
    probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    e = router_de.shape[1]
    # Switch-style load-balance loss
    density = jnp.mean(jax.nn.one_hot(ids[:, 0], e), axis=0)
    mean_probs = jnp.mean(full, axis=0)
    lb = e * jnp.sum(density * mean_probs)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return probs, ids, lb, z


def _expert_ffn_local(xs: jax.Array, group_sizes: jax.Array,
                      wi: jax.Array, wg: jax.Array, wo: jax.Array
                      ) -> jax.Array:
    """xs: (R, D) rows sorted by expert; group_sizes: (E,).

    Runs in the operand dtype (bf16 in production) with f32 accumulation
    (§Perf iter 2b: halves expert-GEMM HBM traffic vs upcasting to f32)."""
    h = jax.lax.ragged_dot(xs, wi.astype(xs.dtype), group_sizes,
                           preferred_element_type=jnp.float32)
    g = jax.lax.ragged_dot(xs, wg.astype(xs.dtype), group_sizes,
                           preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * h).astype(xs.dtype)
    return jax.lax.ragged_dot(h, wo.astype(xs.dtype), group_sizes,
                              preferred_element_type=jnp.float32)


def _moe_local(x2: jax.Array, probs: jax.Array, ids: jax.Array,
               wi: jax.Array, wg: jax.Array, wo: jax.Array,
               num_experts: int) -> jax.Array:
    """Dropless grouped-matmul MoE over a local token batch.

    x2: (T, D); probs/ids: (T, k). Returns (T, D).
    """
    t, k = ids.shape
    flat_ids = ids.reshape(-1)                       # (T*k,)
    order = jnp.argsort(flat_ids)
    token_of = order // k
    xs = jnp.take(x2, token_of, axis=0)              # (T*k, D)
    group_sizes = jnp.bincount(flat_ids, length=num_experts
                               ).astype(jnp.int32)
    ys = _expert_ffn_local(xs.astype(jnp.float32),
                           group_sizes,
                           wi.astype(jnp.float32),
                           wg.astype(jnp.float32),
                           wo.astype(jnp.float32))
    w = jnp.take(probs.reshape(-1), order)           # (T*k,)
    ys = ys * w[:, None]
    out = jnp.zeros_like(x2, dtype=jnp.float32).at[token_of].add(ys)
    return out.astype(x2.dtype)


def _moe_pim(x2: jax.Array, probs: jax.Array, ids: jax.Array,
             wi: ExpertStackedPlan, wg: ExpertStackedPlan,
             wo: ExpertStackedPlan) -> jax.Array:
    """Expert FFN on the PIM engine: dropless weight-stationary mapping.

    Each expert's (D, F) / (F, D) matrices are stationary 'OPCM' arrays;
    the token batch is driven past all of them (broadcast up/gate, paired
    down-projection) and the router weights are applied at aggregation —
    no gather/scatter, matching how a programmed PIM array bank executes.
    x2: (T, D); probs/ids: (T, k). Returns (T, D).

    Expert parallelism rides on the plans, not on this function: when the
    stacks were programmed with a mesh (``engine.program(..., mesh=)`` or
    ``engine.shard_plan_tree``), ``engine_matmul`` runs one expert slab
    per device and all_gathers the (E, T, ·) result, so the combine below
    is unchanged and bit-identical to the single-device route.
    """
    t = x2.shape[0]
    e = wi.num_experts
    x2f = x2.astype(jnp.float32)
    h = engine_matmul(x2f, wi)                       # (E, T, F)
    g = engine_matmul(x2f, wg)                       # (E, T, F)
    hidden = jax.nn.silu(g) * h                      # (E, T, F)
    y = engine_matmul(hidden, wo, paired=True)       # (E, T, D)
    w = jnp.zeros((t, e), jnp.float32)
    w = w.at[jnp.arange(t)[:, None], ids].add(probs)
    return jnp.einsum("te,etd->td", w, y).astype(x2.dtype)


def _moe_ep_body(x2, probs, ids, wi, wg, wo, *, num_experts: int,
                 ep_axis: str, capacity_factor: float = 1.25):
    """shard_map body: wi/wg/wo hold the LOCAL expert slice.

    Perf structure (EXPERIMENTS.md §Perf iter 2): after the expert sort,
    only the first ``cap ~= T·k/ep_size · cf (cf=1.25)`` rows can belong
    to local experts (statistically balanced routing over >=32k tokens), so the
    gather / grouped-matmul / scatter run on a 16x smaller row block
    instead of carrying 15/16 trash rows; the combine psum runs in the
    activation dtype (bf16 on TPU) instead of f32.
    """
    e_local = wi.shape[0]
    ep_size = num_experts // e_local
    t, k = ids.shape
    shard = jax.lax.axis_index(ep_axis)
    e0 = shard * e_local
    local = ids - e0
    valid = (local >= 0) & (local < e_local)
    # invalid assignments go to a trailing trash bucket (sorted last)
    flat_ids = jnp.where(valid, local, e_local).reshape(-1)
    order = jnp.argsort(flat_ids)
    cap = max(1, min(t * k, int(t * k / ep_size * capacity_factor)))
    keep = order[:cap]                      # local assignments sort first
    token_of = keep // k
    xs = jnp.take(x2, token_of, axis=0)
    counts = jnp.bincount(flat_ids, length=e_local + 1)[:e_local]
    # clip group sizes so sum(group_sizes) <= cap (overflow tokens drop —
    # standard capacity-based MoE behaviour)
    cum = jnp.minimum(jnp.cumsum(counts), cap)
    group_sizes = jnp.diff(cum, prepend=0).astype(jnp.int32)
    ys = _expert_ffn_local(xs, group_sizes, wi, wg, wo)
    # zero rows past sum(groups) (ragged_dot leaves them undefined)
    row = jnp.arange(cap)
    in_groups = row < group_sizes.sum()
    w = jnp.take(probs.reshape(-1), keep) * \
        jnp.take(valid.reshape(-1).astype(jnp.float32), keep)
    ys = ys * (w * in_groups.astype(jnp.float32))[:, None]
    out = jnp.zeros(x2.shape, jnp.float32).at[token_of].add(ys)
    return jax.lax.psum(out.astype(x2.dtype), ep_axis)


def moe_apply(p: Params, x: jax.Array, experts_per_token: int,
              aux: Optional[dict] = None) -> jax.Array:
    """MoE FFN. x: (B, S, D) -> (B, S, D). Auto-selects EP when a sharding
    context with a 'model' axis is active."""
    b, s, d = x.shape
    wi_edf = p["wi_edf"]
    pim_experts = isinstance(wi_edf, ExpertStackedPlan)
    num_experts = wi_edf.num_experts if pim_experts else wi_edf.shape[0]
    x2 = x.reshape(-1, d)
    probs, ids, lb, z = _route(p["router_de"], x2, experts_per_token)
    if aux is not None:
        aux["moe_lb_loss"] = aux.get("moe_lb_loss", 0.0) + lb
        aux["moe_z_loss"] = aux.get("moe_z_loss", 0.0) + z

    ctx = current_context()
    if pim_experts:
        # expert stacks are programmed 'OPCM' plans: run the engine route
        # (single-host serving path; EP sharding keeps float stacks)
        out2 = _moe_pim(x2, probs, ids, wi_edf, p["wg_edf"], p["wo_efd"])
    elif ctx is not None and "model" in ctx.mesh.axis_names and \
            ctx.mesh.shape["model"] > 1 and \
            num_experts % ctx.mesh.shape["model"] == 0:
        mesh = ctx.mesh
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        tok_spec = P(batch_axes if batch_axes else None, None)
        body = functools.partial(_moe_ep_body, num_experts=num_experts,
                                 ep_axis="model")
        out2 = shard_map(
            body, mesh=mesh,
            in_specs=(tok_spec, tok_spec, tok_spec,
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=tok_spec,
            check_vma=False,
        )(x2, probs, ids, p["wi_edf"], p["wg_edf"], p["wo_efd"])
    else:
        out2 = _moe_local(x2, probs, ids, p["wi_edf"], p["wg_edf"],
                          p["wo_efd"], num_experts)

    out = out2.reshape(b, s, d)
    if "shared" in p:
        from repro.models.layers import mlp_apply
        out = out + mlp_apply(p["shared"], x)
    return out


def moe_reference(p: Params, x: jax.Array, experts_per_token: int
                  ) -> jax.Array:
    """Dense oracle: evaluate every expert for every token (tests only)."""
    b, s, d = x.shape
    x2 = x.reshape(-1, d).astype(jnp.float32)
    probs, ids, _, _ = _route(p["router_de"], x2, experts_per_token)
    h = jnp.einsum("td,edf->tef", x2, p["wi_edf"].astype(jnp.float32))
    g = jnp.einsum("td,edf->tef", x2, p["wg_edf"].astype(jnp.float32))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * h,
                   p["wo_efd"].astype(jnp.float32))
    e = p["wi_edf"].shape[0]
    w = jnp.zeros((x2.shape[0], e), jnp.float32)
    w = w.at[jnp.arange(x2.shape[0])[:, None], ids].add(probs)
    out = jnp.einsum("te,ted->td", w, y).astype(x.dtype).reshape(b, s, d)
    if "shared" in p:
        from repro.models.layers import mlp_apply
        out = out + mlp_apply(p["shared"], x)
    return out
