"""Plan persistence: serialize programmed 'OPCM' plans into checkpoints.

Serving restarts can skip re-programming (quantize + nibble-decompose +
pad) by saving the planned parameter tree once and restoring it on boot:

  engine.save_plans(dir, plans)            # after plan_params_for_pim
  plans, step, extras = engine.load_plans(dir)

Rides on :mod:`repro.checkpoint.ckpt` (atomic publish, LATEST pointer,
elastic restore): the plan tree's array leaves go into ``arrays.npz`` like
any parameter tree, while a JSON *plan spec* — plan kinds, logical dims,
and each plan's full :class:`~repro.core.pim.PimConfig` including its
substrate name — travels in the manifest's ``extras``. ``load_plans``
rebuilds the exact pytree template (plans and all) from that spec, so the
caller needs no template of its own.

Supported trees: arbitrary nestings of dict / list / tuple whose leaves
are arrays or plans (:class:`DensePlan`, :class:`DepthwisePlan`,
:class:`ExpertStackedPlan`) — the shape of the serving stack's planned
parameter tree.
"""
from __future__ import annotations

import dataclasses
import json
import os
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.core import pim

PLANS_EXTRAS_KEY = "engine_plans"


class PlanCorruptionError(ckpt.CheckpointCorruptionError):
    """A persisted plan leaf failed its manifest sha256 on restore (or
    could not be read back). ``leaf_path`` names the offending leaf in
    the plan tree (e.g. ``layers/wq.planes``)."""

    def __init__(self, msg: str, leaf_path: str,
                 leaf_index: Optional[int] = None) -> None:
        super().__init__(msg, leaf_index=leaf_index)
        self.leaf_path = leaf_path


def _leaf_path_name(template: Any, index: Optional[int]) -> str:
    """Human name of flattened leaf ``index`` in a plan-tree template
    (container keys slash-joined, plan fields dot-joined)."""
    if index is None:
        return "<unknown leaf>"
    paths = jax.tree_util.tree_flatten_with_path(template)[0]
    if not 0 <= index < len(paths):
        return f"<leaf {index}>"

    def _part(key) -> str:
        tu = jax.tree_util
        if isinstance(key, tu.DictKey):
            return f"/{key.key}"
        if isinstance(key, tu.SequenceKey):
            return f"/{key.idx}"
        if isinstance(key, tu.GetAttrKey):
            return f".{key.name}"
        if isinstance(key, tu.FlattenedIndexKey):
            return f".{key.key}"   # plan child slot (values/scale/planes/...)
        return f"/{key}"

    return "".join(_part(k) for k in paths[index][0]).lstrip("/.") or "<root>"


# ---------------------------------------------------------------------------
# spec: JSON description of a plan tree (structure + dtypes, no data)
# ---------------------------------------------------------------------------
def _leaf_spec(x) -> Dict[str, Any]:
    return {"shape": [int(d) for d in x.shape], "dtype": str(x.dtype)}


# PimConfig fields that manifests written before their removal still
# carry. None of them chose a stamped plan's route (``engine.program``
# always stamps ``substrate``), so the loader drops them; any other
# unknown field still fails.
_RETIRED_CFG_KEYS = ("analog", "use_pallas", "cell_bits")


def _cfg_spec(cfg: pim.PimConfig) -> Dict[str, Any]:
    return dataclasses.asdict(cfg)


def _cfg_from_spec(spec: Dict[str, Any]) -> pim.PimConfig:
    return pim.PimConfig(**{k: v for k, v in spec.items()
                            if k not in _RETIRED_CFG_KEYS})


def describe_plan_tree(tree: Any) -> Dict[str, Any]:
    """Recursively describe a tree of plans/arrays as JSON-able spec."""
    if isinstance(tree, pim.ExpertStackedPlan):
        out = {"kind": "expert-plan", "num_experts": tree.num_experts,
               "dense": describe_plan_tree(tree.dense)}
        if tree.shard is not None:
            out["shard"] = {"kind": tree.shard.kind, "axis": tree.shard.axis}
        return out
    if isinstance(tree, pim.DensePlan):
        out = {"kind": "dense-plan", "bits": tree.bits, "k": tree.k,
               "n": tree.n, "cfg": _cfg_spec(tree.cfg),
               "leaves": [_leaf_spec(l) for l in
                          (tree.values, tree.scale, tree.planes,
                           tree.padded_scale)]}
        if tree.abft is not None:
            # ABFT checksum record: a {name: leaf} dict child — described
            # key-by-key so the rebuilt template flattens identically
            out["abft"] = {name: _leaf_spec(leaf)
                           for name, leaf in sorted(tree.abft.items())}
        if tree.shard is not None:
            out["shard"] = {"kind": tree.shard.kind, "axis": tree.shard.axis}
        return out
    if isinstance(tree, pim.DepthwisePlan):
        return {"kind": "depthwise-plan", "bits": tree.bits,
                "cfg": _cfg_spec(tree.cfg),
                "leaves": [_leaf_spec(l) for l in
                           (tree.values, tree.scale, tree.planes)]}
    if isinstance(tree, dict):
        return {"kind": "dict",
                "items": {str(k): describe_plan_tree(v)
                          for k, v in tree.items()}}
    if isinstance(tree, (list, tuple)):
        return {"kind": "list" if isinstance(tree, list) else "tuple",
                "items": [describe_plan_tree(v) for v in tree]}
    if hasattr(tree, "shape") and hasattr(tree, "dtype"):
        return {"kind": "leaf", **_leaf_spec(tree)}
    raise TypeError(f"save_plans cannot describe {type(tree).__name__}; "
                    "supported: dict/list/tuple of arrays and plans")


def _zeros(spec: Dict[str, Any]):
    return jnp.zeros(tuple(spec["shape"]), jnp.dtype(spec["dtype"]))


def _shard_stamps(spec: Dict[str, Any], path: str = "") -> list:
    """Collect (path, shard) pairs recorded in a plan-tree spec — the
    stamps :func:`build_plan_template` does NOT restore (a template plan
    carries no placement; only ``_replace_on_mesh`` re-stamps them)."""
    kind = spec.get("kind")
    out = []
    if kind in ("dense-plan", "expert-plan") and spec.get("shard"):
        out.append((path or "<root>", spec["shard"]))
    if kind == "dict":
        for k, v in spec["items"].items():
            out += _shard_stamps(v, f"{path}/{k}" if path else k)
    elif kind in ("list", "tuple"):
        for i, v in enumerate(spec["items"]):
            out += _shard_stamps(v, f"{path}[{i}]")
    elif kind == "expert-plan":
        out += _shard_stamps(spec["dense"], f"{path}.dense")
    return out


def build_plan_template(spec: Dict[str, Any]) -> Any:
    """Rebuild a zero-filled pytree template from a plan-tree spec."""
    kind = spec["kind"]
    if kind == "expert-plan":
        return pim.ExpertStackedPlan(
            dense=build_plan_template(spec["dense"]),
            num_experts=spec["num_experts"])
    if kind == "dense-plan":
        z = [_zeros(l) for l in spec["leaves"]]
        abft = None
        if spec.get("abft"):
            abft = {name: _zeros(l) for name, l in spec["abft"].items()}
        return pim.DensePlan(values=z[0], scale=z[1], planes=z[2],
                             padded_scale=z[3], bits=spec["bits"],
                             k=spec["k"], n=spec["n"],
                             cfg=_cfg_from_spec(spec["cfg"]), abft=abft)
    if kind == "depthwise-plan":
        z = [_zeros(l) for l in spec["leaves"]]
        return pim.DepthwisePlan(values=z[0], scale=z[1], planes=z[2],
                                 bits=spec["bits"],
                                 cfg=_cfg_from_spec(spec["cfg"]))
    if kind == "dict":
        return {k: build_plan_template(v) for k, v in spec["items"].items()}
    if kind in ("list", "tuple"):
        items = [build_plan_template(v) for v in spec["items"]]
        return items if kind == "list" else tuple(items)
    if kind == "leaf":
        return _zeros(spec)
    raise ValueError(f"unknown plan-spec kind {kind!r}")


# ---------------------------------------------------------------------------
# save / load
# ---------------------------------------------------------------------------
def save_plans(directory: str, plans: Any, step: int = 0,
               extras: Optional[Dict[str, Any]] = None) -> str:
    """Persist a tree of programmed plans (and interleaved arrays).

    The substrate name and full PimConfig of every plan land in the
    manifest ``extras`` (under :data:`PLANS_EXTRAS_KEY`), so a restart can
    both rebuild the tree and audit what operating point it was programmed
    for. Returns the published checkpoint path."""
    all_extras = dict(extras or {})
    all_extras[PLANS_EXTRAS_KEY] = describe_plan_tree(plans)
    return ckpt.save_checkpoint(directory, step, plans, extras=all_extras)


def _replace_on_mesh(tree: Any, spec: Dict[str, Any], mesh) -> Any:
    """Re-place a restored plan tree over ``mesh`` per the saved spec.

    Plans whose spec recorded a shard are re-stamped and device_put with
    the same split (the geometry transforms — column trim, row padding —
    are idempotent, so re-sharding an already-trimmed/padded plan is pure
    placement); everything else is replicated."""
    from repro.engine import mesh as mesh_mod
    kind = spec["kind"]
    if kind in ("dense-plan", "expert-plan"):
        shard = spec.get("shard")
        if shard is not None:
            return mesh_mod.shard_plan(tree, mesh, shard["kind"],
                                       axis=shard["axis"])
        return mesh_mod.replicate(tree, mesh)
    if kind == "dict":
        return {k: _replace_on_mesh(tree[k], v, mesh)
                for k, v in spec["items"].items()}
    if kind in ("list", "tuple"):
        items = [_replace_on_mesh(t, v, mesh)
                 for t, v in zip(tree, spec["items"])]
        return items if kind == "list" else tuple(items)
    # depthwise-plan / leaf: replicate as-is
    return mesh_mod.replicate(tree, mesh)


def load_plans(directory: str, step: Optional[int] = None, *,
               mesh=None) -> Tuple[Any, int, Dict[str, Any]]:
    """Restore a plan tree saved by :func:`save_plans`.

    Returns ``(plans, step, extras)`` with :data:`PLANS_EXTRAS_KEY`
    stripped from ``extras``. With ``mesh=`` the restored tree is
    re-placed over the device mesh: plans saved with a shard stamp get
    the same split back (see :mod:`repro.engine.mesh`), everything else
    is replicated — so a serve restart on a mesh needs no re-programming
    *and* no re-sharding pass. Raises FileNotFoundError when no
    checkpoint exists and ValueError when the checkpoint was not written
    by :func:`save_plans`."""
    if step is None:
        step = ckpt.latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no plan checkpoint under {directory}")
    manifest_path = os.path.join(directory, f"step_{step:08d}",
                                 "manifest.json")
    with open(manifest_path) as f:
        manifest = json.load(f)
    spec = manifest.get("extras", {}).get(PLANS_EXTRAS_KEY)
    if spec is None:
        raise ValueError(
            f"checkpoint at {directory} step {step} has no "
            f"{PLANS_EXTRAS_KEY!r} spec — was it written by save_plans?")
    template = build_plan_template(spec)
    try:
        plans, step, extras = ckpt.restore_checkpoint(directory, template,
                                                      step=step)
    except PlanCorruptionError:
        raise
    except ckpt.CheckpointCorruptionError as e:
        leaf = _leaf_path_name(template, e.leaf_index)
        raise PlanCorruptionError(
            f"plan checkpoint at {directory} step {step} is corrupt: "
            f"leaf {leaf!r} ({e.leaf_name}): {e}", leaf_path=leaf,
            leaf_index=e.leaf_index) from e
    if mesh is not None:
        plans = _replace_on_mesh(plans, spec, mesh)
    else:
        stamps = _shard_stamps(spec)
        if stamps:
            head = ", ".join(
                f"{p}:{s['kind']}@axis{s['axis']}" for p, s in stamps[:3])
            more = f", +{len(stamps) - 3} more" if len(stamps) > 3 else ""
            warnings.warn(
                f"load_plans(mesh=None) drops {len(stamps)} saved shard "
                f"stamp(s) ({head}{more}); plans restore replicated — "
                f"pass mesh= to re-place them", UserWarning, stacklevel=2)
    extras = {k: v for k, v in extras.items() if k != PLANS_EXTRAS_KEY}
    return plans, step, extras
