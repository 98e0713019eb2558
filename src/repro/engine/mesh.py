"""Mesh-sharded execution of programmed PIM plans.

A real OPIMA deployment is a wall of independent optical arrays — the
throughput claim rests on the "inherent massive parallelism within main
memory", i.e. on *many banks in flight*, not on single-array speed. This
module maps that onto a :class:`jax.sharding.Mesh`: a plan's stationary
nibble planes are placed across devices once, at programming time, and
``engine.matmul`` runs the per-device drive through a ``shard_map`` with
the minimal collective epilogue. The split is stamped into the plan as a
:class:`PlanShard` (pytree aux data), so call sites still carry no flags
— the plan itself says how it is laid out, exactly like it says which
substrate it runs on.

Three split kinds, mirroring the tensor-parallel conventions of
:mod:`repro.distributed.sharding` (``_dh`` column-parallel, ``_hd``
row-parallel, ``_edf``/``_efd`` expert stacks):

  ``col``     DensePlan split along N. Every device holds all of K and a
              column block of the planes; outputs are locally complete
              column shards and simply concatenate (no collective on the
              accumulator at all). Bit-identical to single-device on
              ``exact-pallas`` / ``exact-jnp`` / ``emulate``: each output
              column's arithmetic is untouched by the split.
  ``row``     DensePlan split along (padded) K. Activations are quantized
              *globally* first (the per-row dynamic scale needs the full
              K row — the MDL array re-tunes per driven vector), then
              each device contracts its K block to a raw int32
              accumulator and a ``lax.psum`` over the mesh axis sums the
              partials — integer addition, exact under any reassociation
              — before the single dequant epilogue. Bit-identical on the
              integer-datapath substrates (``exact-pallas``/``exact-jnp``).
  ``expert``  ExpertStackedPlan split along the leading expert axis: one
              expert stack per device group. Per-expert math (including
              the per-expert analog auto-range) is self-contained, so an
              ``all_gather`` of the per-expert outputs reconstructs the
              single-device (E, T, N) tensor bit-for-bit on *every*
              substrate; the MoE combine einsum downstream is unchanged.

The ``analog`` substrates refuse dense (row/col) splits: their shared ADC
full scale is a global max over the whole (pairs, chunks, M, N) extent,
so a shard that sees only a subset would auto-range a different lsb —
silently not bit-identical. Expert splits are fine (the range is
per-expert already).

A split axis of size 1 (a data-only mesh) still stamps every plan that
may split: nothing is divided, but the matmul then runs under
``shard_map`` with its batch over the data axes. XLA cannot partition a
Mosaic kernel by itself, so on a TPU an unstamped Pallas plan fed a
data-sharded batch does not compile.

Everything here is CPU-testable with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import pim
from repro.distributed.sharding import DATA_AXES, logical_rules

# substrates whose dense outputs survive each split bit-for-bit
_COL_SUBSTRATES = (pim.EXACT_PALLAS, pim.EXACT_JNP, pim.EMULATE)
_ROW_SUBSTRATES = (pim.EXACT_PALLAS, pim.EXACT_JNP)

SHARD_KINDS = ("col", "row", "expert")


@dataclasses.dataclass(frozen=True)
class PlanShard:
    """How a plan's stationary leaves are split over a mesh.

    Lives in the plan pytree's *aux data* (it must hash/compare like the
    rest of the treedef so jit caches correctly — ``Mesh`` is hashable).
    ``kind`` is one of :data:`SHARD_KINDS`; ``axis`` is the mesh axis the
    stationary dimension is split over (conventionally ``"model"``).
    """

    kind: str
    axis: str
    mesh: Mesh

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]


def _batch_axes(mesh: Mesh, m: int) -> Optional[Tuple[str, ...]]:
    """Mesh axes the flattened batch/token dim may shard over (data
    parallelism riding along a tensor-split matmul), or None when ``m``
    does not divide evenly — replication is always correct."""
    axes = tuple(a for a in DATA_AXES if a in mesh.axis_names)
    if not axes:
        return None
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    return axes if total > 1 and m % total == 0 else None


def _put(leaf: jax.Array, mesh: Mesh, spec: P, base_ndim: int) -> jax.Array:
    """Place one plan leaf; leading stack dims (scan-over-layers vmapped
    programming) shift the spec right, same convention as
    ``param_spec_for_path``."""
    extra = leaf.ndim - base_ndim
    assert extra >= 0, f"leaf rank {leaf.ndim} below base {base_ndim}"
    full = P(*((None,) * extra + tuple(spec)))
    return jax.device_put(leaf, NamedSharding(mesh, full))


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Replicate every array leaf of ``tree`` (plans included) across the
    mesh — correct for any plan, just without tensor parallelism."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


# ---------------------------------------------------------------------------
# Programming-time: stamp + place
# ---------------------------------------------------------------------------
def shard_dense_plan(plan: pim.DensePlan, mesh: Mesh, kind: str,
                     axis: str = "model") -> pim.DensePlan:
    """Split a (possibly layer-stacked) DensePlan over ``mesh[axis]``.

    ``col`` trims the N padding first so shard boundaries never interleave
    pad columns (the kernels re-pad locally per call — correctness is
    unconditional, a non-tile-aligned local N only costs a pad copy);
    ``row`` pads the stationary K so it splits evenly (zero rows are exact
    on the integer datapath). Raises when the plan's substrate cannot stay
    bit-identical under the requested split.
    """
    if kind not in ("col", "row"):
        raise ValueError(f"dense plans shard 'col' or 'row', got {kind!r}")
    tp = mesh.shape[axis]
    sub = plan.substrate
    if tp == 1:
        if sub not in _COL_SUBSTRATES:
            return plan
        # nothing to split, but the stamp still routes the matmul through
        # shard_map with the batch over the data axes: a Mosaic kernel
        # cannot be partitioned automatically
        kind = "col"
    if kind == "col" and sub not in _COL_SUBSTRATES:
        raise ValueError(
            f"substrate {sub!r} cannot column-split bit-identically (the "
            "shared ADC auto-range is a global max); use kind='expert' "
            f"plans or one of {_COL_SUBSTRATES}")
    if kind == "row" and sub not in _ROW_SUBSTRATES:
        raise ValueError(
            f"substrate {sub!r} cannot row-split bit-identically (the "
            "psum epilogue is exact only on the raw int32 accumulator); "
            f"use one of {_ROW_SUBSTRATES}")
    values, scale = plan.values, plan.scale
    planes, padded_scale = plan.planes, plan.padded_scale
    shard = PlanShard(kind=kind, axis=axis, mesh=mesh)
    if kind == "col":
        if plan.n % tp:
            raise ValueError(
                f"col split needs n ({plan.n}) divisible by "
                f"mesh[{axis!r}]={tp}")
        planes = planes[..., :plan.n]
        padded_scale = padded_scale[..., :plan.n]
        specs = (P(None, axis), P(None, axis),
                 P(None, None, axis), P(None, axis))
    else:
        kp = planes.shape[-2]
        pad = (-kp) % tp
        if pad:
            width = [(0, 0)] * planes.ndim
            width[-2] = (0, pad)
            planes = jnp.pad(planes, width)
        specs = (P(None, None), P(None, None),
                 P(None, axis, None), P(None, None))
    values = _put(values, mesh, specs[0], 2)
    scale = _put(scale, mesh, specs[1], 2)
    planes = _put(planes, mesh, specs[2], 3)
    padded_scale = _put(padded_scale, mesh, specs[3], 2)
    return pim.DensePlan(values=values, scale=scale, planes=planes,
                         padded_scale=padded_scale, bits=plan.bits,
                         k=plan.k, n=plan.n, cfg=plan.cfg, shard=shard)


def shard_expert_plan(plan: pim.ExpertStackedPlan, mesh: Mesh,
                      axis: str = "model") -> pim.ExpertStackedPlan:
    """Expert-parallel placement: split every stacked leaf along the
    expert axis — one expert sub-stack per device group. Exact on every
    substrate (per-expert math, including the per-expert analog
    auto-range, is self-contained)."""
    tp = mesh.shape[axis]
    if plan.num_experts % tp:
        raise ValueError(
            f"expert split needs num_experts ({plan.num_experts}) "
            f"divisible by mesh[{axis!r}]={tp}")
    d = plan.dense
    shard = PlanShard(kind="expert", axis=axis, mesh=mesh)
    dense = pim.DensePlan(
        values=_put(d.values, mesh, P(axis, None, None), 3),
        scale=_put(d.scale, mesh, P(axis, None, None), 3),
        planes=_put(d.planes, mesh, P(axis, None, None, None), 4),
        padded_scale=_put(d.padded_scale, mesh, P(axis, None, None), 3),
        bits=d.bits, k=d.k, n=d.n, cfg=d.cfg)
    return pim.ExpertStackedPlan(dense=dense,
                                 num_experts=plan.num_experts, shard=shard)


def shard_plan(plan: pim.Plan, mesh: Mesh, kind: Optional[str] = None,
               axis: str = "model") -> pim.Plan:
    """Stamp + place one plan. ``kind=None`` picks the natural default:
    ``expert`` for expert stacks, ``col`` for dense plans."""
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}; axes: "
                         f"{mesh.axis_names}")
    if isinstance(plan, pim.ExpertStackedPlan):
        if kind not in (None, "expert"):
            raise ValueError(
                f"expert stacks shard kind='expert', got {kind!r}")
        return shard_expert_plan(plan, mesh, axis)
    if isinstance(plan, pim.DensePlan):
        return shard_dense_plan(plan, mesh, kind or "col", axis)
    raise NotImplementedError(
        f"{type(plan).__name__} has no mesh placement (depthwise filters "
        "are below one WDM chunk — shard the channel batch instead)")


def _kind_from_rules(name: str, mesh: Mesh, is_expert: bool
                     ) -> Optional[str]:
    """Derive the split kind for a parameter name from the logical-rule
    table in :mod:`repro.distributed.sharding` — the single source of
    truth for which matmul dimension the 'model' axis partitions."""
    rules = logical_rules(mesh)
    leaf = name[2:] if name.startswith("s_") else name
    if is_expert:
        return "expert"
    for suffix, key in (("_dh", "w_dh"), ("_hd", "w_hd")):
        if leaf.endswith(suffix):
            spec = tuple(rules[key])
            if spec[-1] == "model":
                return "col"
            if spec[0] == "model":
                return "row"
    return None


def shard_plan_tree(tree: Any, mesh: Mesh, axis: str = "model",
                    verbose: bool = False) -> Any:
    """Walk a planned parameter tree (the ``plan_params_for_pim`` output)
    and place every plan on the mesh: tensor-split where the naming
    convention names a split and the geometry divides, replicated
    otherwise. Non-plan leaves are replicated. Always correct — sharding
    only ever falls back to replication, never errors the serve path."""
    def walk(node, name):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            items = [walk(v, name) for v in node]
            return items if isinstance(node, list) else tuple(items)
        if isinstance(node, pim.Plan):
            kind = _kind_from_rules(
                name, mesh, isinstance(node, pim.ExpertStackedPlan))
            if kind is not None:
                try:
                    return shard_plan(node, mesh, kind, axis)
                except ValueError as e:
                    if verbose:
                        print(f"[engine.mesh] {name}: replicating "
                              f"({e})")
            return replicate(node, mesh)
        return replicate(node, mesh)

    return walk(tree, "")


# ---------------------------------------------------------------------------
# Execution: shard_map drives, stamped into the plan — no call-site flags
# ---------------------------------------------------------------------------
def _local_dense(plan: pim.DensePlan, leaves, n: int) -> pim.DensePlan:
    values, scale, planes, padded_scale = leaves
    return pim.DensePlan(values=values, scale=scale, planes=planes,
                         padded_scale=padded_scale, bits=plan.bits,
                         k=plan.k, n=n, cfg=plan.cfg)


def _col_matmul(sub, x: jax.Array, plan: pim.DensePlan,
                cfg: pim.PimConfig, bias: Optional[jax.Array]) -> jax.Array:
    """Column split: every device computes its own complete output
    columns with the unchanged substrate math; the sharded output just
    concatenates. No collective touches the accumulator."""
    sh = plan.shard
    mesh, axis, tp = sh.mesh, sh.axis, sh.size
    orig = x.shape
    x2 = x.reshape(-1, plan.k)
    b = _batch_axes(mesh, x2.shape[0])
    n_local = plan.n // tp
    has_bias = bias is not None

    def body(x_loc, values, scale, planes, padded_scale, *rest):
        local = _local_dense(plan, (values, scale, planes, padded_scale),
                             n_local)
        b_loc = rest[0].reshape(-1) if has_bias else None
        return sub._dense2d(x_loc, local, cfg, b_loc, None)

    in_specs = [P(b, None), P(None, axis), P(None, axis),
                P(None, None, axis), P(None, axis)]
    args = [x2, plan.values, plan.scale, plan.planes, plan.padded_scale]
    if has_bias:
        in_specs.append(P(axis))
        args.append(bias.astype(jnp.float32).reshape(-1))
    out = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                    out_specs=P(b, axis), check_vma=False)(*args)
    return out.reshape(orig[:-1] + (plan.n,))


def _row_matmul(sub, x: jax.Array, plan: pim.DensePlan,
                cfg: pim.PimConfig, bias: Optional[jax.Array]) -> jax.Array:
    """Row (K) split: global dynamic activation quantization (the per-row
    scale needs the whole K row), per-device raw int32 contraction, one
    exact integer ``psum``, then the single dequant epilogue in the same
    op order as both single-device exact routes — bit-identical without a
    bias (a fused Pallas bias contracts to an FMA and may differ by 1
    ulp; here the bias is a separate add, matching ``exact-jnp``)."""
    from repro.kernels.pim_matmul import ops as pim_ops
    sh = plan.shard
    mesh, axis = sh.mesh, sh.axis
    orig = x.shape
    x2 = x.reshape(-1, plan.k)
    a_planes, a_scale = pim.quantize_activations(x2, plan, cfg)
    b = _batch_axes(mesh, x2.shape[0])
    use_ref = sub.name == pim.EXACT_JNP

    def body(ap_loc, planes_loc):
        acc = pim_ops.pim_matmul_int(ap_loc, planes_loc,
                                     interpret=cfg.interpret,
                                     use_ref=use_ref)
        return jax.lax.psum(acc, axis)                  # int32: exact

    acc = shard_map(body, mesh=mesh,
                    in_specs=(P(None, b, axis), P(None, axis, None)),
                    out_specs=P(b, None), check_vma=False
                    )(a_planes, plan.planes)
    out = acc[:, :plan.n].astype(jnp.float32) * a_scale * plan.scale
    if bias is not None:
        out = out + bias.astype(jnp.float32).reshape(1, -1)
    return out.reshape(orig[:-1] + (plan.n,))


def _expert_matmul(sub, x: jax.Array, plan: pim.ExpertStackedPlan,
                   cfg: pim.PimConfig, bias: Optional[jax.Array],
                   paired: bool) -> jax.Array:
    """Expert split: each device group drives its own expert sub-stack
    (vmapped dense math, self-contained per expert) and an ``all_gather``
    along the expert axis reconstructs the exact single-device (E, ..., N)
    tensor — the MoE combine einsum downstream is unchanged, so this is
    the all-to-all-free spelling of expert-parallel routing for the
    drive-all-experts weight-stationary mapping."""
    sh = plan.shard
    mesh, axis = sh.mesh, sh.axis
    d = plan.dense

    def body(x_loc, values, scale, planes, padded_scale):
        local = _local_dense(d, (values, scale, planes, padded_scale), d.n)
        if paired:
            y = jax.vmap(
                lambda xe, dl: sub._dense_nd(xe, dl, cfg, bias, None)
            )(x_loc, local)
        else:
            y = jax.vmap(
                lambda dl: sub._dense_nd(x_loc, dl, cfg, bias, None)
            )(local)
        return jax.lax.all_gather(y, axis, axis=0, tiled=True)

    if paired:
        assert x.ndim >= 2 and x.shape[0] == plan.num_experts, (
            f"paired expert input needs a leading ({plan.num_experts}, "
            f"...) axis, got {x.shape}")
        x_spec = P(axis, *((None,) * (x.ndim - 1)))
    else:
        x_spec = P(*((None,) * x.ndim))
    leaf_spec = P(axis, None, None)
    out = shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, leaf_spec, leaf_spec, P(axis, None, None, None),
                  leaf_spec),
        out_specs=P(*((None,) * (x.ndim + (0 if paired else 1)))),
        check_vma=False,
    )(x, d.values, d.scale, d.planes, d.padded_scale)
    return out


def sharded_matmul(sub, x: jax.Array, plan: pim.Plan, *,
                   cfg: pim.PimConfig, bias: Optional[jax.Array],
                   rng: Optional[jax.Array], paired: bool) -> jax.Array:
    """Dispatch a mesh-stamped plan to its split executor. Reached from
    :meth:`repro.engine.substrates.Substrate.matmul` when
    ``plan.shard is not None`` — call sites are oblivious."""
    sh = plan.shard
    if rng is not None:
        raise NotImplementedError(
            "stochastic analog read noise is not supported on mesh-"
            "sharded plans; program the noise-study plan without a mesh")
    if isinstance(plan, pim.ExpertStackedPlan):
        return _expert_matmul(sub, x, plan, cfg, bias, paired)
    if paired:
        raise ValueError("paired=True is only meaningful for "
                         "ExpertStackedPlan")
    if sh.kind == "col":
        return _col_matmul(sub, x, plan, cfg, bias)
    if sh.kind == "row":
        return _row_matmul(sub, x, plan, cfg, bias)
    raise ValueError(f"unknown shard kind {sh.kind!r} on "
                     f"{type(plan).__name__}")


__all__ = ["PlanShard", "SHARD_KINDS", "shard_plan", "shard_dense_plan",
           "shard_expert_plan", "shard_plan_tree", "replicate",
           "sharded_matmul"]
