"""Plain reference of a w4a4 ResNet of basic blocks, imports nothing of
the program.

Semantics, as the configuration states them:
  - weights drawn from the seed exactly as the network's initializer
    draws them (one key per layer, in layer order; He-normal convs,
    1/sqrt(fan-in) dense), biases zero, batch norm folded away;
  - each conv is an im2col GEMM over SAME-padded patches in (row, col,
    channel) order; every GEMM quantizes its weight to ``weight_bits``
    per output column and its input rows to ``act_bits``, multiplies the
    codes in integers and dequantizes as (acc * row_scale) * col_scale;
  - ReLU after the stem and each c1; the block's c2 and its shortcut (a
    1x1 conv where the shape changes) are summed, then ReLU; global
    average pooling; a dense head; all in ``compute_dtype`` at the
    highest matmul precision.

The check recomputes the logits of the sampled batches from the same
images and compares them with what the window produced: the widest
difference of a logit, as a share of the largest reference logit. The
control is the same reference computed in bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness import counts, seeds, traffic


def _qmax(bits: int) -> int:
    return (1 << (bits - 1)) - 1


def _quantize(x, bits: int, axis: int):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-8) / _qmax(bits)
    codes = jnp.clip(jnp.round(x / scale), -_qmax(bits), _qmax(bits))
    return codes.astype(jnp.int8), scale


def _qmm(x, w, bits: int):
    codes_w, scale_w = w
    codes_a, scale_a = _quantize(x, bits, -1)
    acc = jax.lax.dot(codes_a, codes_w, preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * scale_a.astype(jnp.float32) * scale_w
    return out.astype(x.dtype)


def _weights(key, cfg: Dict) -> Dict:
    layers = counts.resnet_layers(cfg)
    bits = cfg["pim"]["weight_bits"]
    ks = jax.random.split(key, len(layers))
    out = {}
    for k, l in zip(ks, layers):
        if l["kind"] == "conv":
            fan_in = l["k"] * l["k"] * l["cin"]
            w = jax.random.normal(k, (l["k"], l["k"], l["cin"], l["cout"]))
            w = (w * jnp.sqrt(2.0 / fan_in)).reshape(-1, l["cout"])
        else:
            w = jax.random.normal(k, (l["cin"], l["cout"]))
            w = w / jnp.sqrt(l["cin"])
        out[l["name"]] = _quantize(w, bits, 0)
    return out


def _conv(x, w, l: Dict, bits: int):
    k, s = l["k"], l["stride"]
    ph = (k - 1) // 2
    x = jnp.pad(x, ((0, 0), (ph, k - 1 - ph), (ph, k - 1 - ph), (0, 0)))
    oh = -(-l["hw"] // s)
    cols = jnp.concatenate([x[:, i:i + oh * s:s, j:j + oh * s:s, :]
                            for i in range(k) for j in range(k)], axis=-1)
    b = cols.shape[0]
    y = _qmm(cols.reshape(b * oh * oh, -1), w, bits)
    return y.reshape(b, oh, oh, l["cout"])


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _forward(weights, x, cfg_items):
    cfg = dict(cfg_items)
    cfg["pim"] = dict(cfg["pim"])
    bits = cfg["pim"]["act_bits"]
    layers = {l["name"]: l for l in counts.resnet_layers(cfg)}
    relu = jax.nn.relu
    x = relu(_conv(x, weights["stem"], layers["stem"], bits))
    for s, (_, blocks, _) in enumerate(cfg["stages"]):
        for b in range(blocks):
            n = f"s{s}b{b}"
            h = relu(_conv(x, weights[n + "c1"], layers[n + "c1"], bits))
            h = _conv(h, weights[n + "c2"], layers[n + "c2"], bits)
            sc = _conv(x, weights[n + "ds"], layers[n + "ds"], bits) \
                if n + "ds" in weights else x
            x = relu(h + sc)
    x = jnp.mean(x, axis=(1, 2))
    return _qmm(x, weights["fc"], bits).astype(jnp.float32)


def _freeze(cfg: Dict):
    keep = ("image_size", "num_classes", "stem_channels", "kernel_size")
    items = {k: cfg[k] for k in keep}
    items["stages"] = tuple(tuple(s) for s in cfg["stages"])
    items["pim"] = tuple(sorted(cfg["pim"].items()))
    return tuple(sorted(items.items()))


def check(cfg: Dict, mix: Dict, seed: int, samples: List[Dict],
          control: bool = False) -> Dict[str, float]:
    """``logit_err``: the widest difference between a logit the window
    produced and the reference's, over the sampled batches, as a share of
    the largest reference logit. With ``control``, the same reading of
    the bfloat16 reference (``control_logit_err``)."""
    if not samples:
        return {}
    frozen = _freeze(cfg)
    with jax.default_matmul_precision("highest"):
        weights = jax.jit(lambda k: _weights(k, cfg))(
            seeds.jax_key(seed, seeds.WEIGHTS))
        pool = traffic.images(mix, cfg["image_size"], seed)
        refs, got, ctl = [], [], []
        for smp in samples:
            x = pool[smp["slot"]]
            refs.append(np.asarray(_forward(weights, x, frozen)))
            got.append(smp["logits"])
            if control:
                ctl.append(np.asarray(_forward(
                    jax.tree_util.tree_map(
                        lambda a: a.astype(jnp.bfloat16)
                        if a.dtype == jnp.float32 else a, weights),
                    x.astype(jnp.bfloat16), frozen)))
    ref = np.concatenate(refs)
    scale = float(np.max(np.abs(ref)))
    out = {"logit_err": float(np.max(np.abs(np.concatenate(got) - ref)))
           / scale,
           "argmax_agree_share": float(np.mean(
               np.concatenate(got).argmax(-1) == ref.argmax(-1))),
           "images_checked": int(ref.shape[0])}
    if control:
        c = np.concatenate(ctl)
        out["control_logit_err"] = float(np.max(np.abs(c - ref))) / scale
        out["control_argmax_agree_share"] = float(np.mean(
            c.argmax(-1) == ref.argmax(-1)))
    return out
