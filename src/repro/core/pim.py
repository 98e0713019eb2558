"""The OPIMA PIM datapath math (paper §IV.C–D) — plans, programming, and
the per-substrate arithmetic.

This module is the *math* layer of the PIM engine: it defines the operating
point (:class:`PimConfig`), the plan pytree hierarchy (weights programmed
into 'OPCM'), the programming routines (quantize + nibble-decompose + pad,
all once), and the exact / analog / emulation arithmetic that each
execution substrate runs. The *dispatch* layer — the string-keyed substrate
registry that models and serving code talk to — lives in
:mod:`repro.engine`; model code never selects a route with booleans, it
executes plans whose config names a substrate.

The paper's datapath, as reproduced here:

  1. Weights are *programmed once* into 'OPCM': :func:`prepare_weights`
     quantizes (per-output-channel symmetric), nibble-decomposes into 4-bit
     planes — one OPCM cell per nibble (§IV.C.4 TDM) — and pre-pads the
     planes to the Pallas kernel's tile multiples *and* to WDM-chunk
     boundaries, so the exact and analog substrates all consume the same
     stationary layout with no per-call weight re-pad. The result is a
     :class:`DensePlan` pytree; plane decomposition and padding happen at
     programming time, **not** per matmul call (the PIM property: weights
     stay stationary in the array, only activations move).
  2. Activations are dynamically quantized per row — the MDL array re-tunes
     per driven vector (§IV.C.2) — and nibble-decomposed the same way.
  3. Every (act-nibble, weight-nibble) plane pair is one "one-shot" array
     multiply; partial products accumulate over the K (column/wavelength)
     dimension — WDM in-waveguide interference.
  4. The aggregation unit recombines planes with shift-and-add and rescales.
     On the ``exact-pallas`` substrate this runs inside the Pallas kernel's
     fused epilogue: per-row act-scale × per-column weight-scale
     dequantization (+ optional bias) is applied to the int32 accumulator
     tile in VMEM, bit-for-bit equal to :func:`reference_quantized_matmul`.

Plan hierarchy (all registered pytrees, each carrying its
substrate-stamped :class:`PimConfig`):

  DensePlan          (K, N) projection programmed as stationary planes
  DepthwisePlan      (K, C) per-channel filters for grouped convolutions
  ExpertStackedPlan  (E, K, N) vmapped plans over an expert axis (MoE)

Programming (the single place weight decomposition happens):

  prepare_weights(w, cfg)            -> DensePlan
  plan_from_qtensor(w_q, cfg)        -> DensePlan (adopt existing codes)
  prepare_depthwise_weights(w, cfg)  -> DepthwisePlan
  prepare_expert_weights(w, cfg)     -> ExpertStackedPlan
  reference_quantized_matmul(x, w_q) -> oracle the exact substrates must
    match bit-for-bit.

Driving a dense plan is two steps. :func:`quantize_activations` is the
one place a dense matmul's activations are quantized, split into planes
and padded to the plan's K; it returns the (Pa, M, Kp) int8 planes and
(M, 1) f32 row scales. Each integer substrate's drive
(``*_planes_matmul2d``) takes that pair. A producer that builds the pair
itself (a conv's im2col) drives a plan through
``engine.matmul_planes``. Callers go through :mod:`repro.engine`:
``engine.program(w, cfg)`` once, ``engine.matmul(x, plan)`` many.
"""
from __future__ import annotations

import contextvars
import dataclasses
import warnings
from typing import Callable, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.cell import DEFAULT_CELL
from repro.quant.nibbles import (NIBBLE_BASE, from_nibbles, num_nibbles,
                                 to_nibbles)
from repro.quant.quantize import QTensor, quantize

# Canonical substrate names (registry keys — see repro/engine/substrates.py).
EXACT_PALLAS = "exact-pallas"
EXACT_JNP = "exact-jnp"
ANALOG = "analog"
ANALOG_PALLAS = "analog-pallas"
EMULATE = "emulate"

# ``jax.named_scope`` names the CNN executor and the engine's drives
# put on their ops. A profiler's device events carry no metadata, so a
# trace reduction attributes an op to these through the compiled
# program's ``op_name`` of the instruction (``compiled.as_text()``). None
# contains "pim_matmul" or "forward", which trace readers match by name.
SCOPE_IM2COL = "im2col"          # SAME pad, strided slices, concatenate
                                 # (dense conv on an integer substrate:
                                 # the round and planes of each tap too)
SCOPE_POOL = "pool"              # stage bridge, a dense head's max-pool
                                 # or global average
SCOPE_ACT_QUANT = "act_quant"    # per-row scale, round, planes, K pad
                                 # (dense conv on an integer substrate:
                                 # the scale)
SCOPE_KERNEL = "kernel"          # the pallas_call, its tile pads, slice
SCOPE_RESIDUAL = "residual"      # a block's add and ReLU
SCOPES = (SCOPE_IM2COL, SCOPE_POOL, SCOPE_ACT_QUANT, SCOPE_KERNEL,
          SCOPE_RESIDUAL)


@dataclasses.dataclass(frozen=True)
class PimConfig:
    """Operating point of the PIM engine.

    ``substrate`` names the route: one of the registry keys in
    :mod:`repro.engine.substrates` (``exact-pallas``, ``exact-jnp``,
    ``analog``, ``analog-pallas``, ``emulate``). The cell density the
    datapath assumes is :attr:`repro.core.arch.OpimaArch.cell_bits`.
    """
    weight_bits: int = 4          # paper baseline: 4b (one cell per weight)
    act_bits: int = 4
    adc_bits: int = 5             # aggregation-unit ADC resolution
    wdm_chunk: int = 8            # products summed IN ANALOG before one ADC
                                  # conversion. OPIMA uses wavelength-specific
                                  # PDs (§IV.C.4), so in-waveguide interference
                                  # accumulates only across the subarrays of a
                                  # group sharing a wavelength (≈ kernel rows),
                                  # not across the full K dimension.
    substrate: str = EXACT_PALLAS  # registry key of the route
    read_noise_sigma: float = 0.0  # relative transmission read noise; if 0
                                   # and analog, uses the cell-DSE implied one
    interpret: Optional[bool] = None  # Pallas interpret mode; None ->
                                      # per-backend (interpreter off-TPU,
                                      # compiled Mosaic on TPU) via
                                      # kernels.runtime.resolve_interpret
    verify: str = "off"           # ABFT checksum policy: "off" | "sample"
                                  # | "always" (repro.reliability.abft).
                                  # Non-"off" at programming time appends
                                  # the checksum record to the plan; at
                                  # execute time it checks the int32
                                  # accumulator row-sums (exact routes)
                                  # or a noise-banded float row-sum +
                                  # storage audit (analog routes)
    abft_tag: Optional[str] = None  # violation-report tag (the plan's
                                    # tree path in a serving params tree;
                                    # quarantine keys on it)

    @property
    def weight_planes(self) -> int:
        return num_nibbles(self.weight_bits)

    @property
    def act_planes(self) -> int:
        return num_nibbles(self.act_bits)


DEFAULT_PIM = PimConfig()

# Cell-DSE implied read-noise sigma, evaluated once at import: the cell
# model uses host-side float() math, so it must not run inside a jit trace
# (the analog substrate now serves under jit'd prefill/decode).
_IMPLIED_READ_NOISE_SIGMA = float(DEFAULT_CELL.level_noise_sigma())
_warned_noiseless_analog = False


# ---------------------------------------------------------------------------
# Plan hierarchy — weights programmed into 'OPCM'
# ---------------------------------------------------------------------------
class Plan:
    """Marker base for programmed ('planned') weights.

    Every concrete plan is a registered pytree carrying the
    :class:`PimConfig` it was built for; ``plan.substrate`` names the
    execution substrate, so ``engine.matmul(x, plan)`` needs no mode flags
    at call sites.
    """

    cfg: PimConfig

    @property
    def substrate(self) -> str:
        return self.cfg.substrate

    def dequantized(self) -> jax.Array:
        """Float weights implied by the programmed codes (emulation)."""
        return self.values.astype(jnp.float32) * self.scale


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DensePlan(Plan):
    """A weight matrix programmed into 'OPCM': quantized codes plus the
    precomputed int8 nibble planes, pre-padded to the kernel's tile
    multiples. Built once by :func:`prepare_weights`; every subsequent
    execution drives activations past these stationary planes without
    re-running the decomposition.

    Registered as a pytree so plans flow through jit / scan / vmap — the
    serving stack stores one stacked plan per scanned layer.
    """

    values: jax.Array            # int8 codes (K, N), unpadded
    scale: jax.Array             # f32 (1, N), unpadded
    planes: jax.Array            # int8 (Pw, Kp, Np), padded to tile multiples
    padded_scale: jax.Array      # f32 (1, Np) — kernel-epilogue weight scale
    bits: int = 4                # logical weight bit width
    k: int = 0                   # logical contraction dim (planes[:, :k])
    n: int = 0                   # logical output dim (planes[..., :n])
    cfg: PimConfig = DEFAULT_PIM  # operating point the plan was built for
    shard: Optional[object] = None  # PlanShard (engine/mesh.py) when the
                                    # plan is split over a device mesh
    abft: Optional[dict] = None  # ABFT checksum record (col_i32 (K,),
                                 # col_f32 (K,), scale_sum ()) computed at
                                 # programming time when cfg.verify is not
                                 # "off" — see repro.reliability.abft.
                                 # None flattens to zero extra leaves, so
                                 # legacy plans/checkpoints keep their
                                 # leaf count

    @property
    def shape(self):
        return (self.k, self.n)

    # pytree plumbing -----------------------------------------------------
    def tree_flatten(self):
        return ((self.values, self.scale, self.planes, self.padded_scale,
                 self.abft),
                (self.bits, self.k, self.n, self.cfg, self.shard))

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, scale, planes, padded_scale, abft = children
        return cls(values=values, scale=scale, planes=planes,
                   padded_scale=padded_scale, bits=aux[0], k=aux[1],
                   n=aux[2], cfg=aux[3], shard=aux[4], abft=abft)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DepthwisePlan(Plan):
    """Per-channel planned weights for grouped (depthwise) convolutions:
    each channel's (kh*kw,) filter is its own stationary column."""

    values: jax.Array            # int8 codes (K, C)
    scale: jax.Array             # f32 (1, C)
    planes: jax.Array            # int8 (Pw, K, C)
    bits: int = 4
    cfg: PimConfig = DEFAULT_PIM

    def tree_flatten(self):
        return ((self.values, self.scale, self.planes), (self.bits, self.cfg))

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, scale, planes = children
        return cls(values=values, scale=scale, planes=planes, bits=aux[0],
                   cfg=aux[1])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ExpertStackedPlan(Plan):
    """Vmapped plans over a leading expert axis (MoE expert stacks).

    ``dense`` holds a :class:`DensePlan` whose array leaves carry an extra
    leading ``(E, ...)`` dimension — the result of vmapping the programming
    routine over the expert axis. Execution vmaps the dense substrate math
    the same way, so exact substrates stay bit-identical to a per-expert
    reference. This closes the MoE ``_edf``/``_efd`` gap: expert weights
    run on the real engine instead of the fake-quantize emulation.
    """

    dense: DensePlan             # leaves stacked over a leading expert axis
    num_experts: int = 0
    shard: Optional[object] = None  # PlanShard: expert-parallel placement

    @property
    def cfg(self) -> PimConfig:  # type: ignore[override]
        return self.dense.cfg

    @property
    def bits(self) -> int:
        return self.dense.bits

    def dequantized(self) -> jax.Array:
        return self.dense.dequantized()

    @property
    def shape(self):
        return (self.num_experts, self.dense.k, self.dense.n)

    def tree_flatten(self):
        return ((self.dense,), (self.num_experts, self.shard))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(dense=children[0], num_experts=aux[0], shard=aux[1])


# ---------------------------------------------------------------------------
# Programming — the single place weight decomposition happens
# ---------------------------------------------------------------------------
def plan_from_qtensor(w_q: QTensor, cfg: PimConfig = DEFAULT_PIM
                      ) -> DensePlan:
    """Plan already-quantized (K, N) codes: decompose into nibble planes and
    pre-pad them to the plan's layout (``plan_padding``)."""
    from repro.kernels.pim_matmul.pim_matmul import plan_padding
    if cfg.weight_bits != w_q.bits:
        # adopted codes define the weight width; the stamped cfg must agree
        # with plan.bits or engine.matmul's consistency guard rejects it
        cfg = dataclasses.replace(cfg, weight_bits=w_q.bits)
    k, n = w_q.values.shape
    planes = to_nibbles(w_q.values, w_q.bits)              # (Pw, K, N)
    kp, np_ = plan_padding(k, n)
    pad_k, pad_n = kp - k, np_ - n
    # Also land K on a WDM-chunk boundary so the analog substrates consume
    # the same pre-padded planes with no per-call re-pad (chunk boundaries
    # are absolute, so trailing zeros are exact on every route; for the
    # default chunk=8 this is always already satisfied when k >= 512).
    chunk = min(cfg.wdm_chunk, k) if cfg.wdm_chunk > 0 else k
    pad_k += (-(k + pad_k)) % chunk
    if pad_k or pad_n:
        planes = jnp.pad(planes, ((0, 0), (0, pad_k), (0, pad_n)))
    padded_scale = jnp.pad(jnp.broadcast_to(w_q.scale, (1, n)),
                           ((0, 0), (0, pad_n)))
    abft = None
    if cfg.verify != "off":
        # programming-time ABFT checksum column: sum_n of the codes (and
        # of the dequantized columns / the scale row). Verified against
        # the accumulator row-sums at every execute when cfg.verify asks
        from repro.reliability import abft as abft_mod
        if cfg.verify not in abft_mod.VERIFY_MODES:
            raise ValueError(f"unknown verify mode {cfg.verify!r}; "
                             f"expected one of {abft_mod.VERIFY_MODES}")
        abft = abft_mod.checksums(w_q.values, jnp.broadcast_to(w_q.scale,
                                                               (1, n)))
    return DensePlan(values=w_q.values, scale=w_q.scale, planes=planes,
                     padded_scale=padded_scale, bits=w_q.bits, k=k, n=n,
                     cfg=cfg, abft=abft)


def prepare_weights(w: jax.Array, cfg: PimConfig = DEFAULT_PIM) -> DensePlan:
    """Program a weight matrix into 'OPCM': per-output-channel symmetric
    quantization + nibble decomposition + kernel pre-padding, all once.
    w: (K, N) -> DensePlan with codes (K, N), scale (1, N)."""
    assert w.ndim == 2, "prepare_weights expects (K, N)"
    return plan_from_qtensor(quantize(w, bits=cfg.weight_bits, axis=(0,)),
                             cfg)


def prepare_depthwise_weights(w: jax.Array, cfg: PimConfig = DEFAULT_PIM
                              ) -> DepthwisePlan:
    """Program depthwise filters (K=kh*kw, C) with per-channel scales."""
    assert w.ndim == 2, "prepare_depthwise_weights expects (K, C)"
    w_q = quantize(w, bits=cfg.weight_bits, axis=(0,))
    return DepthwisePlan(
        values=w_q.values, scale=w_q.scale,
        planes=to_nibbles(w_q.values, w_q.bits), bits=w_q.bits, cfg=cfg)


def prepare_expert_weights(w: jax.Array, cfg: PimConfig = DEFAULT_PIM
                           ) -> ExpertStackedPlan:
    """Program an expert-stacked weight tensor (E, K, N): one stationary
    'OPCM' array per expert, vmapped over the expert axis."""
    assert w.ndim == 3, "prepare_expert_weights expects (E, K, N)"
    dense = jax.vmap(lambda m: prepare_weights(m, cfg))(w)
    return ExpertStackedPlan(dense=dense, num_experts=w.shape[0])


# ---------------------------------------------------------------------------
# Exact math (bit-sliced integer datapath)
# ---------------------------------------------------------------------------
def _plane_matmuls(a_planes: jax.Array, w_planes: jax.Array) -> jax.Array:
    """All (act-plane, weight-plane) integer matmuls.

    a_planes: (Pa, M, K) int8; w_planes: (Pw, K, N) int8.
    Returns (Pa, Pw, M, N) int32 partial products.
    """
    return jnp.einsum("amk,wkn->awmn", a_planes.astype(jnp.int32),
                      w_planes.astype(jnp.int32),
                      preferred_element_type=jnp.int32)


def _shift_add(partials: jax.Array) -> jax.Array:
    """Aggregation-unit recombination: sum_d sum_e partial[d,e] 16^(d+e).

    Runs in int32. Intermediate shifted terms may exceed int32 range for
    8-bit operands, but two's-complement wraparound addition is associative
    and the *final* sum always fits (|code| <= 127, so |dot| <= 127^2*K),
    so the result is exact — verified bit-for-bit against the un-sliced
    oracle in tests.
    """
    pa, pw = partials.shape[0], partials.shape[1]
    sh_a = 16 ** jnp.arange(pa, dtype=jnp.int32)
    sh_w = 16 ** jnp.arange(pw, dtype=jnp.int32)
    shifts = sh_a[:, None] * sh_w[None, :]
    return jnp.tensordot(shifts, partials.astype(jnp.int32),
                         axes=[[0, 1], [0, 1]])


def _check_widths(cfg: PimConfig) -> None:
    if cfg.weight_bits > 8 or cfg.act_bits > 8:
        raise NotImplementedError(
            "exact int32 shift-and-add supports operand widths <= 8 bits "
            "(the paper evaluates 4b and 8b); wider operands would need an "
            "int64/float accumulation path")


def quantize_activations(x2: jax.Array, plan: DensePlan, cfg: PimConfig
                         ) -> Tuple[jax.Array, jax.Array]:
    """Drive (M, K) float rows into ``plan``'s layout: per-row dynamic
    quantization at ``cfg.act_bits`` (the MDL array re-tunes per driven
    vector), nibble decomposition, and K zero-padded to the plan's
    programmed K (the per-call half of the padding contract; the weight
    half happened once at programming time). Returns the int8 planes
    (Pa, M, Kp) and the f32 per-row scales (M, 1) that every integer
    substrate's drive takes."""
    with jax.named_scope(SCOPE_ACT_QUANT):
        a_q = quantize(x2, bits=cfg.act_bits, axis=(1,))
        ap = to_nibbles(a_q.values, cfg.act_bits)          # (Pa, M, K)
        pad_k = plan.planes.shape[1] - plan.k
        if pad_k:
            ap = jnp.pad(ap, ((0, 0), (0, 0), (0, pad_k)))
    return ap, a_q.scale


# ---------------------------------------------------------------------------
# ABFT verification (repro.reliability.abft does the checksum math; these
# helpers adapt it to each substrate's intermediates and post the result)
# ---------------------------------------------------------------------------
def _act_codes(ap: jax.Array, plan: DensePlan) -> jax.Array:
    """The (M, K) activation codes that planes ``ap`` recombine to, at
    the plan's logical K (the padded columns hold zero planes)."""
    return from_nibbles(ap)[:, :plan.k]


def _abft_report_exact(rowsum: jax.Array, ap: jax.Array,
                       plan: DensePlan, cfg: PimConfig) -> None:
    """Exact-substrate check: int32 accumulator row-sums against the
    checksum-column matvec (bit-exact, wraparound-safe)."""
    from repro.reliability import abft as abft_mod
    viol = abft_mod.int_violations(rowsum, _act_codes(ap, plan), plan.abft,
                                   plan.scale, mode=cfg.verify,
                                   tag=cfg.abft_tag)
    abft_mod.report(cfg.abft_tag, viol)


def _abft_report_float(out: jax.Array, expected: jax.Array, extra_tol,
                       plan: DensePlan, cfg: PimConfig) -> None:
    """Float-substrate check: banded output row-sums plus the exact plane/
    scale storage audits (which carry the deterministic detection)."""
    from repro.reliability import abft as abft_mod
    out = out.astype(jnp.float32)
    # 1e-3 relative band absorbs float re-association across N <= 4096
    tol = extra_tol + 1e-3 * jnp.abs(out).sum(axis=1) + 1e-6
    viol = abft_mod.float_violations(out.sum(axis=1), expected, tol,
                                     plan.planes, plan.abft, plan.scale,
                                     k=plan.k, mode=cfg.verify,
                                     tag=cfg.abft_tag)
    abft_mod.report(cfg.abft_tag, viol)


def _analog_rowsum_tolerance(a_scale: jax.Array, plan: DensePlan,
                             cfg: PimConfig, chunk: int, sigma: float
                             ) -> jax.Array:
    """Static upper bound on the analog readout's row-sum error: per-ADC
    rounding (half an LSB at the worst-case full scale chunk*15^2) plus a
    6-sigma transmission-noise margin, accumulated over chunks and plane
    pairs, scaled by the live dequantization scales. Deliberately loose —
    the exact storage audits do the fault detection; this band only flags
    gross runtime corruption the stores cannot see."""
    from repro.kernels.analog_readout.ref import inv_half_levels
    digit_max = float(NIBBLE_BASE - 1)
    pa = num_nibbles(cfg.act_bits)
    pw = plan.planes.shape[-3]
    s16 = float(sum(16 ** d for d in range(pa))
                * sum(16 ** e for e in range(pw)))
    kp = plan.planes.shape[-2]
    n_chunks = max(-(-kp // max(chunk, 1)), 1)
    lsb_bound = chunk * digit_max ** 2 * inv_half_levels(cfg.adc_bits)
    per_col = n_chunks * s16 * (0.5 * lsb_bound
                                + 6.0 * sigma * digit_max ** 2
                                * chunk ** 0.5)
    return a_scale[:, 0] * jnp.abs(plan.scale).sum() * per_col


def _abft_report_analog(out: jax.Array, ap: jax.Array, a_scale: jax.Array,
                        plan: DensePlan, cfg: PimConfig, chunk: int,
                        sigma: float, bias: Optional[jax.Array]) -> None:
    expected = a_scale[:, 0] * (
        _act_codes(ap, plan).astype(jnp.float32) @ plan.abft["col_f32"])
    if bias is not None:
        expected = expected + bias.astype(jnp.float32).sum()
    _abft_report_float(out, expected,
                       _analog_rowsum_tolerance(a_scale, plan, cfg, chunk,
                                                sigma), plan, cfg)


# ---------------------------------------------------------------------------
# The integer substrates' drives: each takes the (Pa, M, Kp) planes and
# (M, 1) scales of quantize_activations (or of a conv's im2col planes)
# ---------------------------------------------------------------------------
def exact_jnp_planes_matmul2d(ap: jax.Array, a_scale: jax.Array,
                              plan: DensePlan, cfg: PimConfig,
                              bias: Optional[jax.Array] = None,
                              verify: bool = False) -> jax.Array:
    """``exact-jnp`` substrate: integer plane matmuls + shift-and-add in
    plain jnp, dequantized eagerly. Bit-identical to the Pallas route
    without a bias; the kernel's fused bias contracts mul+add to an FMA
    (one rounding) and may differ from this two-step add by 1 ulp."""
    acc = _shift_add(_plane_matmuls(ap[:, :, :plan.k],
                                    plan.planes[:, :plan.k, :plan.n]))
    if verify:
        _abft_report_exact(acc.sum(axis=1), ap, plan, cfg)
    out = acc.astype(jnp.float32) * a_scale * plan.scale
    if bias is not None:
        out = out + bias.astype(jnp.float32).reshape(1, -1)
    return out


def _pad_bias(bias: Optional[jax.Array], plan: DensePlan
              ) -> Optional[jax.Array]:
    """Broadcast + pad an (N,) bias to the plan's padded column count for
    a kernel's fused epilogue."""
    if bias is None:
        return None
    pad_n = plan.planes.shape[2] - plan.n
    return jnp.pad(bias.astype(jnp.float32).reshape(1, -1),
                   ((0, 0), (0, pad_n)))


@dataclasses.dataclass(frozen=True)
class KernelCall:
    """One ``pim_matmul`` call as traced: where it was, its logical
    (M, K, N), the (Mp, Kp, Np) the kernel issues, the tiles (bm, bn, bk)
    its grid runs on, and its activation and weight plane counts.

    Two contracts meet here. The plan's padding (``plan_padding``) fixes
    Kp and Np once, when the weights are programmed; the call's tiles
    (``kernel_tiles``) divide them and are chosen per call from (M, Kp,
    Np). Mp is M rounded up to whole M tiles: the last one may be
    partial, and the planes are never padded along M. The grid runs
    (Mp/bm) x (Np/bn) x (Kp/bk) steps."""
    scope: str                   # name stack at the call, "s0b0c1/kernel"
    m: int
    k: int
    n: int
    mp: int
    kp: int
    np: int
    pa: int
    pw: int
    bm: int
    bn: int
    bk: int


_CALL_LOG: contextvars.ContextVar[Optional[List[KernelCall]]] = \
    contextvars.ContextVar("kernel_call_log", default=None)


def kernel_calls(fn: Callable, *args) -> List[KernelCall]:
    """Trace ``fn(*args)`` for shapes only (nothing is compiled or run)
    and return the exact-pallas kernel calls it makes, in order.

    ``args`` may be arrays or ``jax.ShapeDtypeStruct``. JAX reuses a
    function's earlier trace at the same shapes, which would skip the
    calls: a fresh closure is traced each time, and ``fn`` must be the
    plain function, not a ``jax.jit`` of it (``jitted.__wrapped__``)."""
    log: List[KernelCall] = []
    token = _CALL_LOG.set(log)
    try:
        jax.eval_shape(lambda *a: fn(*a), *args)
    finally:
        _CALL_LOG.reset(token)
    return log


def _log_kernel_call(m: int, pa: int, plan: DensePlan,
                     want_rowsum: bool) -> None:
    log = _CALL_LOG.get()
    if log is None:
        return
    from jax._src import source_info_util
    from repro.kernels.pim_matmul.pim_matmul import issued_tiles
    pw, k, n = plan.planes.shape
    bm, bn, bk, kp, np_ = issued_tiles(m, k, n, pa, pw, want_rowsum)
    log.append(KernelCall(
        scope=str(source_info_util.current_name_stack()), m=m, k=plan.k,
        n=plan.n, mp=-(-m // bm) * bm, kp=kp, np=np_, pa=pa, pw=pw, bm=bm,
        bn=bn, bk=bk))


def exact_pallas_planes_matmul2d(ap: jax.Array, a_scale: jax.Array,
                                 plan: DensePlan, cfg: PimConfig,
                                 bias: Optional[jax.Array] = None,
                                 verify: bool = False) -> jax.Array:
    """``exact-pallas`` substrate: the Pallas kernel whose fused epilogue
    applies per-row act-scale × per-col weight-scale + optional bias on
    the int32 accumulator tile in VMEM. With ``verify`` the kernel also
    returns the int32 accumulator row-sums from the epilogue for the ABFT
    check (padded columns hold zero planes, so the padded row-sum equals
    the logical one)."""
    from repro.kernels.pim_matmul import ops as pim_ops
    _check_widths(cfg)
    with jax.named_scope(SCOPE_KERNEL):
        _log_kernel_call(ap.shape[1], ap.shape[0], plan, want_rowsum=verify)
        res = pim_ops.pim_matmul_fused(ap, plan.planes, a_scale,
                                       plan.padded_scale,
                                       bias=_pad_bias(bias, plan),
                                       interpret=cfg.interpret,
                                       want_rowsum=verify)
        if verify:
            out, rowsum = res
            _abft_report_exact(rowsum, ap, plan, cfg)
            return out[:, :plan.n]
        return res[:, :plan.n]


# ---------------------------------------------------------------------------
# Analog readout math
# ---------------------------------------------------------------------------
# The readout-chain arithmetic itself (chunked photodetector sums ->
# transmission noise -> shared auto-ranged ADC -> integer code accumulation
# -> shift-and-add -> dequant epilogue) lives in
# repro/kernels/analog_readout/: ``ref.py`` is the whole-array jnp oracle
# the ``analog`` substrate runs, and the fused Pallas kernel behind
# ``analog-pallas`` must match it bit-for-bit on the deterministic path.
# Both substrates consume the same pre-padded plan layout the exact
# kernels use (planes + padded_scale; K lands on WDM-chunk boundaries at
# programming time), so there is no per-call weight re-pad on any route.

def _resolve_analog_sigma(cfg: PimConfig, rng: Optional[jax.Array]
                          ) -> float:
    """The transmission-noise sigma an analog substrate should model.

    An explicitly requested ``read_noise_sigma > 0`` without a key raises
    (the noise must not silently vanish); with ``read_noise_sigma == 0``
    the cell-DSE implied sigma applies when a key is given, and without a
    key the model degrades — with a once-per-process warning — to the
    deterministic ADC-only transfer the serving path relies on."""
    sigma = cfg.read_noise_sigma
    if sigma > 0.0 and rng is None:
        raise ValueError(
            "analog substrate with an explicit read_noise_sigma > 0 "
            "requires an rng key (pass rng=, or leave read_noise_sigma=0 "
            "for the deterministic ADC-only readout)")
    if sigma == 0.0:
        global _warned_noiseless_analog
        if rng is None and not _warned_noiseless_analog:
            # once per process: loud enough for accuracy studies without
            # repeating at every trace site in a jit'd serving stack
            _warned_noiseless_analog = True
            warnings.warn(
                "analog readout without an rng key models the "
                "deterministic transfer only (ADC quantization, no "
                "transmission noise); pass rng= for the noise study",
                stacklevel=3)
        sigma = _IMPLIED_READ_NOISE_SIGMA
    return sigma


def _analog_chunk(plan: DensePlan, cfg: PimConfig) -> int:
    """The WDM chunk length; ``wdm_chunk <= 0`` means one chunk spans all
    of K, the same fallback the programming-time chunk padding uses."""
    return min(cfg.wdm_chunk, plan.k) if cfg.wdm_chunk > 0 else plan.k


def analog_planes_matmul2d(ap: jax.Array, a_scale: jax.Array,
                           plan: DensePlan, cfg: PimConfig,
                           bias: Optional[jax.Array] = None,
                           rng: Optional[jax.Array] = None,
                           verify: bool = False) -> jax.Array:
    """``analog`` substrate: the whole-array jnp readout oracle — it
    materializes the full (planes, chunks, M, N) chunk-sum tensor, which
    makes it the slow-but-transparent accuracy-study twin of
    ``analog-pallas``."""
    from repro.kernels.analog_readout.ref import analog_readout_fused_ref
    chunk = _analog_chunk(plan, cfg)
    sigma = _resolve_analog_sigma(cfg, rng)
    sigma_eff = sigma if rng is not None else 0.0
    out = analog_readout_fused_ref(
        ap, plan.planes, a_scale, plan.padded_scale, chunk,
        cfg.adc_bits, sigma=sigma_eff, rng=rng
    )[:, :plan.n]
    if bias is not None:
        out = out + bias.astype(jnp.float32).reshape(1, -1)
    if verify:
        _abft_report_analog(out, ap, a_scale, plan, cfg, chunk, sigma_eff,
                            bias)
    return out


def analog_pallas_planes_matmul2d(ap: jax.Array, a_scale: jax.Array,
                                  plan: DensePlan, cfg: PimConfig,
                                  bias: Optional[jax.Array] = None,
                                  rng: Optional[jax.Array] = None,
                                  verify: bool = False) -> jax.Array:
    """``analog-pallas`` substrate: the fused Pallas analog-readout kernel
    — chunked PD sums, optional threaded-key transmission noise, shared
    auto-ranged ADC, integer code accumulation, and the recombination/
    dequant epilogue all in VMEM tiles. Bit-identical to
    :func:`analog_planes_matmul2d` on the deterministic (``rng=None``)
    path; statistically consistent under noise (different PRNG streams)."""
    from repro.kernels.analog_readout import ops as analog_ops
    chunk = _analog_chunk(plan, cfg)
    sigma = _resolve_analog_sigma(cfg, rng)
    seed = None
    if rng is not None:
        # threaded key: the kernel folds grid coordinates into this seed
        # per tile (vmap-safe — expert stacks batch it like any operand)
        seed = jax.random.randint(rng, (), 0, jnp.iinfo(jnp.int32).max,
                                  dtype=jnp.int32)
    sigma_eff = sigma if rng is not None else 0.0
    out = analog_ops.analog_matmul_fused(
        ap, plan.planes, a_scale, plan.padded_scale, seed,
        _pad_bias(bias, plan), chunk=chunk, adc_bits=cfg.adc_bits,
        sigma=sigma_eff, interpret=cfg.interpret)
    out = out[:, :plan.n]
    if verify:
        _abft_report_analog(out, ap, a_scale, plan, cfg, chunk, sigma_eff,
                            bias)
    return out


# ---------------------------------------------------------------------------
# Emulation math (weight-quantization-only; the old serve escape hatch)
# ---------------------------------------------------------------------------
def emulate_matmul2d(x2: jax.Array, plan: DensePlan, cfg: PimConfig,
                     bias: Optional[jax.Array] = None,
                     verify: bool = False) -> jax.Array:
    """``emulate`` substrate: float matmul against the dequantized codes.

    Models the *weight* programming (cell-density quantization) only — no
    dynamic activation quantization, no integer datapath. Numerically the
    quantize-dequantize ('fake quantize') emulation serving historically
    used, now a first-class substrate."""
    out = x2.astype(jnp.float32) @ plan.dequantized()
    if bias is not None:
        out = out + bias.astype(jnp.float32).reshape(1, -1)
    if verify:
        expected = x2.astype(jnp.float32) @ plan.abft["col_f32"]
        if bias is not None:
            expected = expected + bias.astype(jnp.float32).sum()
        _abft_report_float(out, expected, 0.0, plan, cfg)
    return out


# ---------------------------------------------------------------------------
# Depthwise (grouped-convolution) math
# ---------------------------------------------------------------------------
def depthwise_exact_matmul(x: jax.Array, plan: DepthwisePlan,
                           cfg: PimConfig) -> jax.Array:
    """Grouped (depthwise) convolution through the bit-sliced engine.

    Each channel's patch vector is one driven vector against that channel's
    stationary filter column: integer plane products + shift-and-add per
    channel, dequantized with per-(row, channel) act scales × per-channel
    weight scales. Exact on every substrate (the analog readout study
    covers the GEMM layers; depthwise K = kh*kw is below one WDM chunk).

    x: (..., K, C) float patches — K = kh*kw taps, C channels -> (..., C).
    """
    orig_shape = x.shape
    k, c = orig_shape[-2], orig_shape[-1]
    x3 = x.reshape(-1, k, c)
    a_q = quantize(x3, bits=cfg.act_bits, axis=(1,))       # scale (M, 1, C)
    a_planes = to_nibbles(a_q.values, cfg.act_bits)        # (Pa, M, K, C)
    partials = jnp.einsum("amkc,wkc->awmc",
                          a_planes.astype(jnp.int32),
                          plan.planes.astype(jnp.int32),
                          preferred_element_type=jnp.int32)
    acc = _shift_add(partials)                             # (M, C) int32
    out = acc.astype(jnp.float32) * a_q.scale[:, 0, :] * plan.scale
    return out.reshape(orig_shape[:-2] + (c,))


def depthwise_emulate_matmul(x: jax.Array, plan: DepthwisePlan,
                             cfg: PimConfig) -> jax.Array:
    """``emulate`` substrate depthwise route: float einsum against the
    dequantized per-channel filters."""
    return jnp.einsum("...kc,kc->...c", x.astype(jnp.float32),
                      plan.dequantized())


def reference_quantized_matmul(x: jax.Array,
                               w_q: Union[DensePlan, QTensor],
                               cfg: PimConfig = DEFAULT_PIM) -> jax.Array:
    """Oracle: plain int32 matmul of the quantized codes (no nibble
    decomposition). Exact substrates must match this bit-for-bit."""
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1])
    a_q = quantize(x2, bits=cfg.act_bits, axis=(1,))
    acc = jnp.einsum("mk,kn->mn", a_q.values.astype(jnp.int32),
                     w_q.values.astype(jnp.int32),
                     preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * a_q.scale * w_q.scale
    return out.reshape(orig_shape[:-1] + (w_q.values.shape[-1],))
