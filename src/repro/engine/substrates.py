"""Execution substrates for the OPIMA PIM engine.

A *substrate* is one way of realizing the paper's weight-stationary
datapath in software. Each implements the same two-verb interface —
``program`` (place weights into stationary 'OPCM' form, once) and
``matmul`` (drive activations past the programmed plan, many times) — and
registers under a string key, so models and serving code select behavior
by name instead of by boolean flag tangles. This mirrors how real PIM
systems expose programmability to software (Ghose et al.; Hassanpour
et al.: the ISA is "program array" + "drive vector", not "pick a branch").

Registered substrates:

  ``exact-pallas``  bit-exact integer datapath through the Pallas kernel
                    with the fused dequant epilogue (the default).
  ``exact-jnp``     the same integer math in plain jnp — bit-identical to
                    ``exact-pallas`` on the bias-free path (a fused bias
                    contracts to an FMA in the kernel and may differ by
                    1 ulp); the portable fallback / oracle twin.
  ``analog``        physical-readout model (per-WDM-chunk photodetector
                    sums, transmission noise, ADC quantization) — the
                    whole-array jnp oracle, slow but transparent.
  ``analog-pallas`` the same readout model through the fused Pallas
                    analog-readout kernel: the chain runs on VMEM tiles,
                    no (planes, chunks, M, N) intermediate touches HBM.
                    Bit-identical to ``analog`` with ``rng=None``;
                    statistically consistent under noise. The
                    physically-faithful mode that serves at speed.
  ``emulate``       weight-quantization-only float matmul (the historical
                    serve.py fake-quantize escape hatch, now first-class).

All substrates share the programming math in :mod:`repro.core.pim`, so a
plan programmed by one substrate carries the same codes/planes as any
other; only the drive arithmetic differs. ``matmul`` dispatches on the
plan type (:class:`~repro.core.pim.DensePlan`,
:class:`~repro.core.pim.DepthwisePlan`,
:class:`~repro.core.pim.ExpertStackedPlan`), so call sites need no
shape-role flags either.

A dense drive is split in two. :func:`repro.core.pim.quantize_activations`
turns (M, K) float rows into (Pa, M, Kp) int8 planes and (M, 1) scales,
once, for every integer substrate; each of those substrates implements
only ``_drive``, which takes that pair. ``matmul_planes`` hands ``_drive``
a pair its caller built itself (a conv's im2col planes). ``emulate`` has
no integer datapath and keeps a float ``_dense2d`` of its own.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax

from repro.core import pim


class Substrate:
    """Base execution substrate: program-once / drive-many interface.

    Subclasses set ``name`` (the registry key) and ``is_exact`` (whether
    ``matmul`` is bit-identical to
    :func:`repro.core.pim.reference_quantized_matmul`) and implement
    ``_drive`` (or, without an integer datapath, ``_dense2d``). Plan-type
    dispatch (dense / depthwise / expert-stacked), activation reshaping
    and quantization are shared here.
    """

    name: str = ""
    is_exact: bool = False
    # whether matmul runs the int32 bit-sliced datapath (operand-width
    # guarded); float-only routes like ``emulate`` set this False
    integer_datapath: bool = True

    # -- programming ------------------------------------------------------
    def stamp(self, cfg: pim.PimConfig) -> pim.PimConfig:
        """Return ``cfg`` with this substrate recorded as the route, so the
        resulting plan dispatches back here with no flags at call sites."""
        return dataclasses.replace(cfg, substrate=self.name)

    def program(self, w: jax.Array, cfg: pim.PimConfig = pim.DEFAULT_PIM
                ) -> pim.DensePlan:
        """Program a (K, N) weight matrix into a stationary plan."""
        return pim.prepare_weights(w, self.stamp(cfg))

    def program_depthwise(self, w: jax.Array,
                          cfg: pim.PimConfig = pim.DEFAULT_PIM
                          ) -> pim.DepthwisePlan:
        """Program (K=kh*kw, C) depthwise filters, one column per channel."""
        return pim.prepare_depthwise_weights(w, self.stamp(cfg))

    def program_experts(self, w: jax.Array,
                        cfg: pim.PimConfig = pim.DEFAULT_PIM
                        ) -> pim.ExpertStackedPlan:
        """Program an (E, K, N) expert stack, vmapped over the expert axis."""
        return pim.prepare_expert_weights(w, self.stamp(cfg))

    # -- execution --------------------------------------------------------
    def matmul(self, x: jax.Array, plan: pim.Plan, *,
               cfg: Optional[pim.PimConfig] = None,
               bias: Optional[jax.Array] = None,
               rng: Optional[jax.Array] = None,
               paired: bool = False) -> jax.Array:
        """Drive activations past a programmed plan.

        Dense plans take x (..., K) -> (..., N). Depthwise plans take
        x (..., K, C) -> (..., C). Expert-stacked plans broadcast
        x (..., K) to every expert -> (E, ..., N) by default; with
        ``paired=True``, x carries a leading (E, ...) axis and expert i
        sees only x[i] (the MoE down-projection shape).
        """
        cfg = plan.cfg if cfg is None else cfg
        if self.integer_datapath:
            # guard every entry, not just api.matmul; the float-only
            # emulate route legitimately runs wider-than-8-bit operands
            pim._check_widths(cfg)
        if getattr(plan, "shard", None) is not None:
            # mesh-stamped plan: the split executor wraps the same
            # per-substrate math in a shard_map + collective epilogue
            from repro.engine import mesh as mesh_mod
            return mesh_mod.sharded_matmul(self, x, plan, cfg=cfg,
                                           bias=bias, rng=rng,
                                           paired=paired)
        if isinstance(plan, pim.ExpertStackedPlan):
            return self._experts(x, plan, cfg, bias, rng, paired)
        if paired:
            raise ValueError(
                "paired=True is only meaningful for ExpertStackedPlan, "
                f"got {type(plan).__name__}")
        if isinstance(plan, pim.DepthwisePlan):
            if bias is not None:
                raise ValueError(
                    "depthwise plans have no fused bias path; add the "
                    "bias to the engine.matmul result instead")
            return self._depthwise(x, plan, cfg)
        return self._dense_nd(x, plan, cfg, bias, rng)

    def _dense_nd(self, x: jax.Array, plan: pim.DensePlan,
                  cfg: pim.PimConfig, bias: Optional[jax.Array],
                  rng: Optional[jax.Array]) -> jax.Array:
        orig_shape = x.shape
        k = orig_shape[-1]
        assert k == plan.k, f"contraction mismatch {k} vs plan {plan.k}"
        x2 = x.reshape(-1, k)
        out = self._dense2d(x2, plan, cfg, bias, rng)
        return out.reshape(orig_shape[:-1] + (plan.n,))

    def _experts(self, x: jax.Array, plan: pim.ExpertStackedPlan,
                 cfg: pim.PimConfig, bias: Optional[jax.Array],
                 rng: Optional[jax.Array], paired: bool) -> jax.Array:
        run = lambda xe, d, key: self._dense_nd(xe, d, cfg, bias, key)
        keys = None if rng is None else jax.random.split(rng,
                                                         plan.num_experts)
        if paired:
            assert x.ndim >= 2 and x.shape[0] == plan.num_experts, (
                f"paired expert input needs a leading ({plan.num_experts},"
                f" ...) axis, got {x.shape}")
            if keys is None:
                return jax.vmap(lambda xe, d: run(xe, d, None))(x, plan.dense)
            return jax.vmap(run)(x, plan.dense, keys)
        if keys is None:
            return jax.vmap(lambda d: run(x, d, None))(plan.dense)
        return jax.vmap(lambda d, key: run(x, d, key))(plan.dense, keys)

    def matmul_planes(self, ap: jax.Array, a_scale: jax.Array,
                      plan: pim.Plan, *, bias: Optional[jax.Array] = None,
                      rng: Optional[jax.Array] = None) -> jax.Array:
        """Drive activations quantized already past an unsharded
        :class:`~repro.core.pim.DensePlan`: ``ap`` the (Pa, M, Kp) int8
        planes at the plan's padded K, ``a_scale`` the (M, 1) f32 per-row
        scales, as :func:`repro.core.pim.quantize_activations` makes
        them. Returns (M, N) f32. Any other plan, or a substrate without
        an integer datapath, raises ``ValueError``."""
        if not self.integer_datapath:
            raise ValueError(f"substrate {self.name!r} has no integer "
                             "datapath to drive activation planes into")
        if not isinstance(plan, pim.DensePlan):
            raise ValueError("matmul_planes drives a DensePlan, got "
                             f"{type(plan).__name__}")
        if plan.shard is not None:
            raise ValueError("matmul_planes drives an unsharded plan; "
                             "this one is split over a mesh")
        if ap.shape[-1] != plan.planes.shape[1]:
            raise ValueError(f"activation planes are {ap.shape[-1]} deep, "
                             f"the plan's K is {plan.planes.shape[1]}")
        cfg = plan.cfg
        pim._check_widths(cfg)
        return self._drive(ap, a_scale, plan, cfg, bias, rng)

    def _dense2d(self, x2: jax.Array, plan: pim.DensePlan,
                 cfg: pim.PimConfig, bias: Optional[jax.Array],
                 rng: Optional[jax.Array]) -> jax.Array:
        ap, a_scale = pim.quantize_activations(x2, plan, cfg)
        return self._drive(ap, a_scale, plan, cfg, bias, rng)

    def _drive(self, ap: jax.Array, a_scale: jax.Array,
               plan: pim.DensePlan, cfg: pim.PimConfig,
               bias: Optional[jax.Array],
               rng: Optional[jax.Array]) -> jax.Array:
        """(Pa, M, Kp) int8 planes and (M, 1) f32 scales -> (M, N) f32."""
        raise NotImplementedError

    @staticmethod
    def _verify(plan: pim.DensePlan, cfg: pim.PimConfig) -> bool:
        """Whether this dispatch runs ABFT checksum verification: the
        plan must carry a checksum record (programmed with
        ``cfg.verify != "off"``) and the executing config must not have
        switched it off. Sharded plans never reach here (the mesh
        executor runs shard-local matmuls verify-free; cross-shard
        checksums would need a collective epilogue)."""
        return cfg.verify != "off" and getattr(plan, "abft", None) is not None

    def _depthwise(self, x: jax.Array, plan: pim.DepthwisePlan,
                   cfg: pim.PimConfig) -> jax.Array:
        # Depthwise filters (K = kh*kw taps) fit below one WDM chunk, so
        # every substrate but ``emulate`` runs the exact per-channel math.
        return pim.depthwise_exact_matmul(x, plan, cfg)


class ExactPallasSubstrate(Substrate):
    """Bit-exact integer datapath through the fused-epilogue Pallas kernel."""

    name = pim.EXACT_PALLAS
    is_exact = True

    def _drive(self, ap, a_scale, plan, cfg, bias, rng):
        return pim.exact_pallas_planes_matmul2d(
            ap, a_scale, plan, cfg, bias, verify=self._verify(plan, cfg))


class ExactJnpSubstrate(Substrate):
    """Bit-exact integer datapath in plain jnp (portable oracle twin)."""

    name = pim.EXACT_JNP
    is_exact = True

    def _drive(self, ap, a_scale, plan, cfg, bias, rng):
        return pim.exact_jnp_planes_matmul2d(
            ap, a_scale, plan, cfg, bias, verify=self._verify(plan, cfg))


class AnalogSubstrate(Substrate):
    """Physical-readout model: PD chunk sums + noise + ADC quantization
    (whole-array jnp oracle)."""

    name = pim.ANALOG
    is_exact = False

    def _drive(self, ap, a_scale, plan, cfg, bias, rng):
        return pim.analog_planes_matmul2d(
            ap, a_scale, plan, cfg, bias, rng,
            verify=self._verify(plan, cfg))


class AnalogPallasSubstrate(Substrate):
    """The same physical-readout model through the fused Pallas kernel:
    chunk sums, noise, ADC, code accumulation, and the dequant epilogue
    stay in VMEM tiles. Plans are interchangeable with ``analog`` (same
    programming); with ``rng=None`` the outputs are bit-identical."""

    name = pim.ANALOG_PALLAS
    is_exact = False

    def _drive(self, ap, a_scale, plan, cfg, bias, rng):
        return pim.analog_pallas_planes_matmul2d(
            ap, a_scale, plan, cfg, bias, rng,
            verify=self._verify(plan, cfg))


class EmulateSubstrate(Substrate):
    """Weight-quantization-only emulation (float matmul on dequantized
    codes) — models cell-density programming, not the integer datapath.

    Programming is inherited unchanged even though this route only reads
    ``values``/``scale``: keeping every substrate's plans structurally
    identical means a plan can be re-routed to an exact substrate via a
    cfg override (ablations) and persisted checkpoints stay
    substrate-portable, at the cost of nibble planes the emulate matmul
    never touches and a per-call K*N dequantize (``plan.dequantized()``)
    the old store-floats escape hatch avoided — acceptable for a fidelity
    study mode, not a serving-perf path."""

    name = pim.EMULATE
    is_exact = False
    integer_datapath = False

    def _dense2d(self, x2, plan, cfg, bias, rng):
        return pim.emulate_matmul2d(x2, plan, cfg, bias,
                                    verify=self._verify(plan, cfg))

    def _depthwise(self, x, plan, cfg):
        return pim.depthwise_emulate_matmul(x, plan, cfg)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Substrate] = {}


def register_substrate(substrate: Substrate, *, name: Optional[str] = None
                       ) -> Substrate:
    """Register a substrate under ``name`` (default: ``substrate.name``).
    Re-registering a name replaces the previous entry (test seams,
    downstream hardware backends)."""
    key = name or substrate.name
    if not key:
        raise ValueError("substrate must have a non-empty name")
    _REGISTRY[key] = substrate
    return substrate


def get_substrate(name: str) -> Substrate:
    """Look up a substrate by registry key; unknown names raise ValueError
    listing what is available."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown PIM substrate {name!r}; available: "
            f"{', '.join(available_substrates())}") from None


def available_substrates() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_substrate(ExactPallasSubstrate())
register_substrate(ExactJnpSubstrate())
register_substrate(AnalogSubstrate())
register_substrate(AnalogPallasSubstrate())
register_substrate(EmulateSubstrate())
