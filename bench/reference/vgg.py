"""Plain reference of a w4a4 VGG (configuration D of arXiv:1409.1556),
imports nothing of the program.

Semantics, as the configuration states them:
  - weights drawn from the seed exactly as the network's initializer
    draws them (one key per layer, in layer order; He-normal convs,
    1/sqrt(fan-in) dense), biases zero, no batch norm;
  - each conv is a stride-1 im2col GEMM over SAME-padded patches in
    (row, col, channel) order, and every GEMM quantizes its weight to
    ``weight_bits`` per output column and its input rows to ``act_bits``,
    multiplies the codes in integers and dequantizes as
    (acc * row_scale) * col_scale: ``bench/reference/cnn.py``'s
    ``_conv`` and ``_qmm``;
  - ReLU after every conv; a ``pool_size`` x ``pool_size`` max-pool after
    each stage; the last map flattened in (h, w, c) order; the dense
    layers of ``fc`` with ReLU, then the classifier; all in
    ``compute_dtype`` at the highest matmul precision.

The check recomputes the logits of the sampled batches from the same
images, in blocks of at most ``BLOCK`` images (a float32 patch matrix
of a batch of 64 at 224 px is 7.4 GB; every row is quantized on its own,
so a block's logits are the batch's), and compares them with what the
window produced: the widest difference of a logit, as a share of the
largest reference logit. The control is the same reference computed in
bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells, seeds, traffic

BLOCK = 8

_cnn = cells.load_module(cells.BENCH / "reference" / "cnn.py",
                         "reference_cnn")


def _shapes(cfg: Dict) -> List[tuple]:
    """(name, weight shape) of every layer, in the initializer's order."""
    k, hw, c = cfg["kernel_size"], cfg["image_size"], 3
    out = []
    for s, (ch, convs) in enumerate(cfg["stages"]):
        for j in range(convs):
            out.append((f"s{s}c{j}", (k, k, c, ch)))
            c = ch
        hw //= cfg["pool_size"]
    cin = hw * hw * c
    for i, n in enumerate(list(cfg["fc"]) + [cfg["num_classes"]]):
        out.append((f"fc{i + 1}", (cin, n)))
        cin = n
    return out


def _weights(key, cfg: Dict) -> Dict:
    shapes = _shapes(cfg)
    bits = cfg["pim"]["weight_bits"]
    out = {}
    for k, (name, shape) in zip(jax.random.split(key, len(shapes)), shapes):
        w = jax.random.normal(k, shape)
        if len(shape) == 4:
            fan_in = shape[0] * shape[1] * shape[2]
            w = (w * jnp.sqrt(2.0 / fan_in)).reshape(-1, shape[-1])
        else:
            w = w / jnp.sqrt(shape[0])
        out[name] = _cnn._quantize(w, bits, 0)
    return out


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _forward(weights, x, cfg_items):
    cfg = dict(cfg_items)
    bits = dict(cfg["pim"])["act_bits"]
    f = cfg["pool_size"]
    for s, (ch, convs) in enumerate(cfg["stages"]):
        for j in range(convs):
            l = dict(hw=x.shape[1], k=cfg["kernel_size"], stride=1, cout=ch)
            x = jax.nn.relu(_cnn._conv(x, weights[f"s{s}c{j}"], l, bits))
        b, h, w, c = x.shape
        x = x.reshape(b, h // f, f, w // f, f, c).max(axis=(2, 4))
    x = x.reshape(x.shape[0], -1)
    n_fc = len(cfg["fc"]) + 1
    for i in range(n_fc):
        x = _cnn._qmm(x, weights[f"fc{i + 1}"], bits)
        if i < n_fc - 1:
            x = jax.nn.relu(x)
    return x.astype(jnp.float32)


def _freeze(cfg: Dict):
    keep = ("image_size", "num_classes", "kernel_size", "pool_size")
    items = {k: cfg[k] for k in keep}
    items["stages"] = tuple(tuple(s) for s in cfg["stages"])
    items["fc"] = tuple(cfg["fc"])
    items["pim"] = tuple(sorted(cfg["pim"].items()))
    return tuple(sorted(items.items()))


def _logits(weights, x, frozen) -> np.ndarray:
    return np.concatenate([np.asarray(_forward(weights, x[i:i + BLOCK],
                                               frozen))
                           for i in range(0, x.shape[0], BLOCK)])


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
            refs.append(_logits(weights, x, frozen))
            got.append(smp["logits"])
            if control:
                ctl.append(_logits(
                    jax.tree_util.tree_map(
                        lambda a: a.astype(jnp.bfloat16)
                        if a.dtype == jnp.float32 else a, weights),
                    x.astype(jnp.bfloat16), frozen))
    ref = np.concatenate(refs)
    scale = float(np.max(np.abs(ref)))
    got = np.concatenate(got)
    out = {"logit_err": float(np.max(np.abs(got - ref))) / scale,
           "argmax_agree_share": float(np.mean(
               got.argmax(-1) == ref.argmax(-1))),
           "images_checked": int(ref.shape[0])}
    if control:
        c = np.concatenate(ctl)
        out["control_logit_err"] = float(np.max(np.abs(c - ref))) / scale
        out["control_argmax_agree_share"] = float(np.mean(
            c.argmax(-1) == ref.argmax(-1)))
    return out
