"""The OPIMA PIM execution engine — substrate-registry API.

This package is the only way model and serving code touches the PIM
datapath. The paper's machine is one datapath — weights programmed once
into OPCM, activations driven past them — and this API keeps software
shaped the same way:

  from repro import engine

  cfg  = engine.PimConfig(weight_bits=4, act_bits=4,
                          substrate="exact-pallas")
  plan = engine.program(w, cfg)          # program once (quantize +
                                         #   nibble-decompose + pad)
  y    = engine.matmul(x, plan)          # execute many — route comes from
                                         #   the plan, no mode flags

``engine.matmul`` quantizes a dense plan's activations once
(:func:`repro.core.pim.quantize_activations`: per-row scale, nibble
planes, K padded to the plan) and hands the planes to the substrate's
drive. ``engine.matmul_planes(ap, a_scale, plan)`` takes planes its
caller built itself, as the CNN executor's im2col does.

Substrates (string-keyed registry, :mod:`repro.engine.substrates`):
``exact-pallas`` (default; fused-epilogue Pallas kernel, bit-exact),
``exact-jnp`` (same math in jnp, bit-identical), ``analog``
(photodetector/ADC readout model, whole-array jnp), ``analog-pallas``
(the same readout model fused into a Pallas kernel — the fast
physically-faithful route), ``emulate`` (weight-quantization-only float
matmul). ``register_substrate`` admits new backends without touching
call sites.

Plans (:mod:`repro.core.pim`): :class:`DensePlan` (projections),
:class:`DepthwisePlan` (grouped convs), :class:`ExpertStackedPlan`
(vmapped MoE expert stacks). All are registered pytrees carrying their
substrate-stamped :class:`PimConfig`, so they flow through jit/scan/vmap
and serialize with :func:`save_plans` / :func:`load_plans`.
"""
from repro.core.pim import (DEFAULT_PIM, DensePlan, DepthwisePlan,
                            ExpertStackedPlan, PimConfig, Plan,
                            prepare_depthwise_weights, prepare_expert_weights,
                            prepare_weights, reference_quantized_matmul)
from repro.engine.api import matmul, matmul_planes, program
from repro.engine.mesh import (PlanShard, replicate, shard_plan,
                               shard_plan_tree)
from repro.engine.persist import (PlanCorruptionError, load_plans,
                                  save_plans)
from repro.engine.substrates import (AnalogPallasSubstrate, AnalogSubstrate,
                                     EmulateSubstrate, ExactJnpSubstrate,
                                     ExactPallasSubstrate, Substrate,
                                     available_substrates, get_substrate,
                                     register_substrate)

__all__ = [
    "DEFAULT_PIM", "PimConfig",
    "Plan", "DensePlan", "DepthwisePlan", "ExpertStackedPlan",
    "program", "matmul", "matmul_planes",
    "prepare_weights", "prepare_depthwise_weights", "prepare_expert_weights",
    "reference_quantized_matmul",
    "Substrate", "register_substrate", "get_substrate",
    "available_substrates",
    "ExactPallasSubstrate", "ExactJnpSubstrate", "AnalogSubstrate",
    "AnalogPallasSubstrate", "EmulateSubstrate",
    "save_plans", "load_plans", "PlanCorruptionError",
    "PlanShard", "shard_plan", "shard_plan_tree", "replicate",
]
