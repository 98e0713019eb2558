"""JAX CNN models built from the Table-II layer specs (one source of truth
with core/workloads.py). Supports:

  * float forward (training, Table-II accuracy experiments),
  * fake-quantized forward (PTQ accuracy at int8/int4),
  * PIM-executed forward — convs (im2col GEMM) and dense layers run through
    the OPIMA PIM engine (exact bit-sliced or analog mode): the paper's
    deployment path.

The executor is structure-aware, keyed on the builders' deterministic layer
names: ResNet basic blocks (c1/c2/ds + residual), Inception branches
(b1 | b3r→b3 | b5r→b5a→b5b | pool→bp, concatenated), SqueezeNet fire
modules (sq → e1‖e3 concat), MobileNet/VGG sequential. Pooling follows
the specs' spatial bookkeeping: when a conv expects a smaller input than
the current map, a max-pool bridges the gap; a dense layer that flattens
a map states the size it flattens (``DenseSpec.in_h``/``in_w``) and the
map is max-pooled down to it, while one that states none takes the
global average. A map that fits neither raises.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import engine
from repro.core.pim import (SCOPE_ACT_QUANT, SCOPE_IM2COL, SCOPE_POOL,
                            SCOPE_RESIDUAL, DensePlan, PimConfig)
from repro.core.workloads import ConvSpec, DenseSpec, LayerSpec
from repro.quant.nibbles import to_nibbles
from repro.quant.quantize import fake_quantize, quantize, scale_from_amax

Params = Dict[str, Any]


def init_cnn(layers: Sequence[LayerSpec], key) -> Params:
    params: Params = {}
    ks = jax.random.split(key, len(layers))
    for k, spec in zip(ks, layers):
        if isinstance(spec, ConvSpec):
            fan_in = spec.kh * spec.kw * spec.in_c_per_group
            w = jax.random.normal(
                k, (spec.kh, spec.kw, spec.in_c_per_group, spec.out_c))
            params[spec.name] = {"w": w * jnp.sqrt(2.0 / fan_in),
                                 "b": jnp.zeros((spec.out_c,))}
        else:
            w = jax.random.normal(k, (spec.in_features, spec.out_features))
            params[spec.name] = {"w": w / jnp.sqrt(spec.in_features),
                                 "b": jnp.zeros((spec.out_features,))}
    return params


def _same_pad(x: jax.Array, spec: ConvSpec) -> jax.Array:
    """SAME-pad the spatial axes of a (B, H, W, C) map for ``spec``."""
    kh, kw = spec.kh, spec.kw
    ph, pw = (kh - 1) // 2, (kw - 1) // 2
    return jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw), (0, 0)))


def _taps(xp: jax.Array, spec: ConvSpec) -> List[jax.Array]:
    """The kh*kw strided views (B, oh, ow, C) of a SAME-padded map, in
    the patches' (kh, kw) order."""
    s, oh, ow = spec.stride, spec.out_h, spec.out_w
    return [xp[:, i:i + oh * s:s, j:j + ow * s:s, :]
            for i in range(spec.kh) for j in range(spec.kw)]


def _im2col(x: jax.Array, spec: ConvSpec) -> jax.Array:
    """x: (B, H, W, C) -> patches (B, oh, ow, kh*kw*C), SAME padding."""
    with jax.named_scope(SCOPE_IM2COL):
        return jnp.concatenate(_taps(_same_pad(x, spec), spec), axis=-1)


def _im2col_planes(x: jax.Array, spec: ConvSpec, plan: DensePlan
                   ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, H, W, C) -> the int8 planes (Pa, M, Kp) and the f32 scales
    (M, 1) that ``quantize_activations`` of ``_im2col(x, spec)`` at the
    plan's ``act_bits`` gives, K padded to the plan's, with no float
    patch matrix between.

    A patch row's abs max is the max over its kh x kw window of each
    pixel's channel abs max on the padded map: the same maximum of the
    same values, so the scale and every code are bit-identical to
    quantizing the patches. Each tap is quantized and split into planes
    on its own, so the concatenate writes int8 planes only."""
    s, oh, ow = spec.stride, spec.out_h, spec.out_w
    bits = plan.cfg.act_bits
    with jax.named_scope(SCOPE_IM2COL):
        xp = _same_pad(x, spec)
    with jax.named_scope(SCOPE_ACT_QUANT):
        amax = jax.lax.reduce_window(
            jnp.max(jnp.abs(xp), axis=-1), -jnp.inf, jax.lax.max,
            (1, spec.kh, spec.kw), (1, s, s), "VALID")[:, :oh, :ow, None]
        scale = scale_from_amax(amax, bits)
    with jax.named_scope(SCOPE_IM2COL):
        planes = [to_nibbles(quantize(t, bits, scale=scale).values, bits)
                  for t in _taps(xp, spec)]
        pad_k = plan.planes.shape[1] - plan.k
        if pad_k:
            planes.append(jnp.zeros(planes[0].shape[:-1] + (pad_k,),
                                    jnp.int8))
        planes = jnp.concatenate(planes, axis=-1)
    return (planes.reshape(planes.shape[0], -1, planes.shape[-1]),
            scale.reshape(-1, 1))


def _maxpool(x: jax.Array, factor: int) -> jax.Array:
    with jax.named_scope(SCOPE_POOL):
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, factor, factor, 1),
            (1, factor, factor, 1), "VALID")


def _dense_rows(x: jax.Array, spec: DenseSpec) -> jax.Array:
    """A (B, H, W, C) map as the (B, in_features) rows ``spec`` takes:
    max-pooled to its stated in_h x in_w and flattened in (h, w, c)
    order, or, where it states no size, averaged over space."""
    b, h, w, c = x.shape
    if spec.in_h:
        f = h // spec.in_h
        if (h, w) == (f * spec.in_h, f * spec.in_w) and \
                spec.in_features == spec.in_h * spec.in_w * c:
            if f > 1:
                x = _maxpool(x, f)
            return x.reshape(b, -1)
    elif spec.in_features == c:
        with jax.named_scope(SCOPE_POOL):
            return jnp.mean(x, axis=(1, 2))
    stated = f"{spec.in_h}x{spec.in_w}" if spec.in_h else "global average"
    raise ValueError(
        f"dense layer {spec.name!r} takes {spec.in_features} features "
        f"({stated}); a {h}x{w}x{c} map cannot give them")


class _Executor:
    """Structure-aware layer executor.

    With ``pim`` set, every layer's weights are *programmed once* per
    executor through :func:`repro.engine.program` (quantize +
    nibble-decompose + pad at programming time, keyed on the deterministic
    layer name), and activations are driven past the stationary plans on
    whichever substrate ``pim.substrate`` names — the paper's
    weight-stationary OPCM mapping. The layer bias is fused into the
    kernel's dequant epilogue.

    A dense conv on a substrate with an integer datapath (``exact-pallas``,
    ``exact-jnp``, ``analog``, ``analog-pallas``) writes its patches as
    int8 planes straight from the map (``_im2col_planes``) and drives them
    through :func:`repro.engine.matmul_planes`. Without PIM, or on
    ``emulate``, it builds the f32 patches (``_im2col``) and multiplies
    them (:func:`repro.engine.matmul` on ``emulate``). Depthwise convs and
    dense layers always take float rows.
    """

    def __init__(self, params: Params, quant_bits: int = 0,
                 pim: Optional[PimConfig] = None, rng=None,
                 plans: Optional[Dict[str, Any]] = None):
        self.params = params
        self.quant_bits = quant_bits
        self.pim = pim
        self.rng = rng
        # layer name -> programmed plan; pass plan_cnn_weights(...) output
        # to keep weights stationary across forwards
        self._plans: Dict[str, Any] = {} if plans is None else plans

    def _plan(self, name: str, w: jax.Array, depthwise: bool = False):
        plan = self._plans.get(name)
        if plan is None:
            plan = engine.program(w, self.pim,
                                  kind="depthwise" if depthwise else "dense")
            self._plans[name] = plan
        return plan

    def _layer_rng(self, name: str):
        # fold the layer name in so same-shaped layers draw independent
        # analog noise realizations instead of one correlated sample
        if self.rng is None:
            return None
        return jax.random.fold_in(self.rng, zlib.crc32(name.encode()))

    def _weight(self, w: jax.Array) -> jax.Array:
        """A layer's (K, N) weights, fake-quantized per column when
        ``quant_bits`` asks."""
        if self.quant_bits:
            return fake_quantize(w, self.quant_bits, axis=(0,))
        return w

    def matmul(self, x: jax.Array, w: jax.Array, name: str,
               bias: Optional[jax.Array] = None) -> jax.Array:
        if self.pim is not None:
            return engine.matmul(x, self._plan(name, w), cfg=self.pim,
                                 bias=bias, rng=self._layer_rng(name))
        y = x @ w
        return y if bias is None else y + bias

    def _dense_conv(self, spec: ConvSpec, x: jax.Array, w: jax.Array,
                    bias: jax.Array) -> jax.Array:
        """A dense conv as one GEMM over its patch rows."""
        plan = None if self.pim is None else self._plan(spec.name, w)
        if plan is None or \
                not engine.get_substrate(plan.substrate).integer_datapath:
            return self.matmul(_im2col(x, spec), w, spec.name, bias=bias)
        ap, a_scale = _im2col_planes(x, spec, plan)
        y = engine.matmul_planes(ap, a_scale, plan, bias=bias,
                                 rng=self._layer_rng(spec.name))
        return y.reshape(x.shape[0], spec.out_h, spec.out_w, spec.out_c)

    def conv(self, spec: ConvSpec, x: jax.Array, relu: bool = True
             ) -> jax.Array:
        with jax.named_scope(spec.name):
            if x.shape[1] > spec.in_h:                 # stage pooling bridge
                x = _maxpool(x, x.shape[1] // spec.in_h)
            p = self.params[spec.name]
            if spec.groups == 1:
                y = self._dense_conv(
                    spec, x, self._weight(p["w"].reshape(-1, spec.out_c)),
                    p["b"])
            else:                                      # depthwise
                cols = _im2col(x, spec)
                b, oh, ow, _ = cols.shape
                cols = cols.reshape(b, oh, ow, spec.kh * spec.kw, spec.in_c)
                w = self._weight(
                    p["w"].reshape(spec.kh * spec.kw, spec.in_c))
                if self.pim is not None:
                    # per-channel programmed plan through the bit-sliced engine
                    y = engine.matmul(cols,
                                      self._plan(spec.name, w, depthwise=True),
                                      cfg=self.pim)
                else:
                    y = jnp.einsum("bhwkc,kc->bhwc", cols, w)
                y = y + p["b"]
            return jax.nn.relu(y) if relu else y

    def dense(self, spec: DenseSpec, x: jax.Array, relu: bool) -> jax.Array:
        with jax.named_scope(spec.name):
            if x.ndim == 4:
                x = _dense_rows(x, spec)
            y = self.matmul(x, self._weight(self.params[spec.name]["w"]),
                            spec.name, bias=self.params[spec.name]["b"])
            return jax.nn.relu(y) if relu else y


def plan_cnn_weights(params: Params, layers: Sequence[LayerSpec],
                     pim: PimConfig) -> Dict[str, Any]:
    """Program every layer's weights into planned 'OPCM' form once.

    Pass the result as ``cnn_forward(..., plans=...)`` so repeated
    (eager) forwards drive activations past stationary planes instead of
    re-running quantize + nibble-decompose + pad per call. Only valid
    while ``quant_bits == 0`` (plans capture the raw float weights).
    """
    plans: Dict[str, Any] = {}
    for spec in layers:
        p = params[spec.name]
        if isinstance(spec, ConvSpec) and spec.groups != 1:
            w = p["w"].reshape(spec.kh * spec.kw, spec.in_c)
            plans[spec.name] = engine.program(w, pim, kind="depthwise")
        elif isinstance(spec, ConvSpec):
            plans[spec.name] = engine.program(
                p["w"].reshape(-1, spec.out_c), pim)
        else:
            plans[spec.name] = engine.program(p["w"], pim)
    return plans


def cnn_forward(params: Params, layers: Sequence[LayerSpec], x: jax.Array,
                quant_bits: int = 0, pim: Optional[PimConfig] = None,
                rng=None, plans: Optional[Dict[str, Any]] = None
                ) -> jax.Array:
    """x: (B, H, W, 3) -> logits (B, classes)."""
    assert plans is None or not quant_bits, \
        "precomputed plans capture raw float weights; they cannot honor " \
        "quant_bits — pass one or the other"
    ex = _Executor(params, quant_bits, pim, rng, plans)
    specs = list(layers)
    i = 0
    while i < len(specs):
        spec = specs[i]
        name = spec.name
        if isinstance(spec, ConvSpec) and name.endswith(".b1"):
            # Inception block: 7 consecutive specs
            b1s, b3rs, b3s, b5rs, b5as, b5bs, bps = specs[i:i + 7]
            if x.shape[1] > b1s.in_h:
                x = _maxpool(x, x.shape[1] // b1s.in_h)
            b1 = ex.conv(b1s, x)
            b3 = ex.conv(b3s, ex.conv(b3rs, x))
            b5 = ex.conv(b5bs, ex.conv(b5as, ex.conv(b5rs, x)))
            xp = jax.lax.reduce_window(
                x, 0.0, jax.lax.add, (1, 3, 3, 1), (1, 1, 1, 1),
                "SAME") / 9.0
            bp = ex.conv(bps, xp)
            x = jnp.concatenate([b1, b3, b5, bp], axis=-1)
            i += 7
        elif isinstance(spec, ConvSpec) and name.endswith(".sq"):
            # SqueezeNet fire module: sq -> (e1 || e3) concat
            sqs, e1s, e3s = specs[i:i + 3]
            if x.shape[1] > sqs.in_h:
                x = _maxpool(x, x.shape[1] // sqs.in_h)
            sq = ex.conv(sqs, x)
            x = jnp.concatenate([ex.conv(e1s, sq), ex.conv(e3s, sq)],
                                axis=-1)
            i += 3
        elif isinstance(spec, ConvSpec) and name.endswith("c1") and \
                "b" in name:
            # ResNet basic block: c1 -> c2 (+ds shortcut), residual add
            c1s, c2s = specs[i], specs[i + 1]
            has_ds = i + 2 < len(specs) and specs[i + 2].name.endswith("ds")
            saved = x
            h = ex.conv(c2s, ex.conv(c1s, x), relu=False)
            shortcut = ex.conv(specs[i + 2], saved, relu=False) if has_ds \
                else saved
            with jax.named_scope(c2s.name), \
                    jax.named_scope(SCOPE_RESIDUAL):
                x = jax.nn.relu(h + shortcut)
            i += 3 if has_ds else 2
        elif isinstance(spec, ConvSpec):
            last = (i == len(specs) - 1)           # SqueezeNet conv10 head
            x = ex.conv(spec, x, relu=not last)
            i += 1
        else:
            last = (i == len(specs) - 1)
            x = ex.dense(spec, x, relu=not last)
            i += 1
    if x.ndim == 4:
        x = jnp.mean(x, axis=(1, 2))
    return x
