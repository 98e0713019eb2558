"""Top-level engine verbs: ``program`` once, ``matmul`` many.

These functions are the whole execution surface models see:

  plan = engine.program(w, cfg)        # weights -> stationary 'OPCM' plan
  y    = engine.matmul(x, plan)        # activations driven past the plan
  y    = engine.matmul_planes(ap, a_scale, plan)
                                       # activations quantized already

``program`` resolves the substrate from ``cfg`` (or an explicit override)
and stamps it into the plan; ``matmul`` dispatches on the plan's recorded
substrate and type, so call sites carry no mode flags. Plan persistence
(``save_plans`` / ``load_plans``) lives in :mod:`repro.engine.persist`.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core import pim
from repro.engine.substrates import get_substrate

_PROGRAM_KINDS = ("dense", "depthwise", "experts")


def program(w: jax.Array, cfg: pim.PimConfig = pim.DEFAULT_PIM, *,
            kind: str = "dense", substrate: Optional[str] = None,
            mesh: Optional["jax.sharding.Mesh"] = None,
            spec: Optional[str] = None,
            mesh_axis: str = "model") -> pim.Plan:
    """Program weights into a stationary plan on a named substrate.

    Args:
      w: float weights — (K, N) for ``kind="dense"``, (K=kh*kw, C) for
        ``kind="depthwise"``, (E, K, N) for ``kind="experts"``.
      cfg: PIM operating point; its ``substrate`` names the route
        unless ``substrate`` overrides it.
      kind: which plan family to build.
      substrate: optional registry key overriding ``cfg``'s substrate.
      mesh: optional :class:`jax.sharding.Mesh` to split the plan over —
        the sharding is stamped into the plan (like the substrate), so
        ``matmul`` needs no flags. See :mod:`repro.engine.mesh`.
      spec: split kind when ``mesh`` is given — one of ``"col"``,
        ``"row"`` (dense) or ``"expert"`` (expert stacks); ``None``
        defaults to ``"col"`` for dense plans and ``"expert"`` for
        expert stacks.
      mesh_axis: the mesh axis the stationary dimension splits over.

    Returns:
      A :class:`~repro.core.pim.Plan` carrying the substrate-stamped
      config (and, with ``mesh``, the stamped :class:`PlanShard`).
    """
    sub = get_substrate(substrate or cfg.substrate)
    if kind == "dense":
        plan = sub.program(w, cfg)
    elif kind == "depthwise":
        plan = sub.program_depthwise(w, cfg)
    elif kind == "experts":
        plan = sub.program_experts(w, cfg)
    else:
        raise ValueError(f"unknown plan kind {kind!r}; expected one of "
                         f"{_PROGRAM_KINDS}")
    if mesh is not None:
        from repro.engine import mesh as mesh_mod
        plan = mesh_mod.shard_plan(plan, mesh, spec, axis=mesh_axis)
    elif spec is not None:
        raise ValueError("spec= requires mesh=")
    return plan


def matmul(x: jax.Array, plan: pim.Plan, *,
           cfg: Optional[pim.PimConfig] = None,
           bias: Optional[jax.Array] = None,
           rng: Optional[jax.Array] = None,
           paired: bool = False) -> jax.Array:
    """Drive activations past a programmed plan — no mode flags.

    The route is the plan's recorded substrate (``plan.cfg``), overridable
    with an explicit ``cfg`` (ablations that execute one plan on several
    substrates). Shapes follow the plan type:

      DensePlan          x (..., K)    -> (..., N)
      DepthwisePlan      x (..., K, C) -> (..., C)
      ExpertStackedPlan  x (..., K)    -> (E, ..., N)   broadcast, or with
                         ``paired=True``
                         x (E, ..., K) -> (E, ..., N)   expert i sees x[i]

    An override ``cfg`` must agree with the plan's programmed weight
    width: the codes/planes were decomposed at ``plan.bits`` and cannot be
    reinterpreted at another width (activation/ADC knobs may differ — the
    MDL array re-tunes per driven vector). A mismatch raises instead of
    silently mis-dequantizing.

    ``paired`` must be explicit — it is never inferred from shapes, so a
    broadcast batch that happens to equal the expert count cannot silently
    pair. ``bias`` is an optional (N,) dense-plan bias (fused into the
    Pallas epilogue on ``exact-pallas``); ``rng`` feeds the ``analog``
    substrate's stochastic read noise (``None`` with the default implied
    sigma -> deterministic ADC-only readout).
    """
    if cfg is None:
        cfg = plan.cfg
    elif getattr(plan, "bits", None) is not None and \
            cfg.weight_bits != plan.bits:
        pim._check_widths(cfg)   # legacy precedence: wide operands raise
        raise ValueError(
            f"override cfg has weight_bits={cfg.weight_bits} but the plan "
            f"was programmed at {plan.bits} bits; weight width is baked "
            "into the plan at programming time — build the override with "
            "dataclasses.replace(plan.cfg, ...) to change only the route")
    sub = get_substrate(cfg.substrate)
    # operand-width guard runs inside Substrate.matmul, so direct
    # substrate calls are protected too
    return sub.matmul(x, plan, cfg=cfg, bias=bias, rng=rng, paired=paired)


def matmul_planes(ap: jax.Array, a_scale: jax.Array, plan: pim.Plan, *,
                  bias: Optional[jax.Array] = None,
                  rng: Optional[jax.Array] = None) -> jax.Array:
    """Drive activations that are quantized already past a plan, on the
    plan's substrate: ``ap`` (Pa, M, Kp) int8 planes at the plan's padded
    K and ``a_scale`` (M, 1) f32 per-row scales, the pair
    :func:`repro.core.pim.quantize_activations` makes from float rows.
    Returns (M, N) f32, bit-identical to ``matmul`` on the rows those
    planes quantize. For a producer that writes the planes itself (a
    conv's im2col); serves an unsharded ``DensePlan`` on a substrate with
    an integer datapath and raises ``ValueError`` for anything else."""
    return get_substrate(plan.substrate).matmul_planes(
        ap, a_scale, plan, bias=bias, rng=rng)
