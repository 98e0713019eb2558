"""Plain reference of a w4a4 decoder LM (Qwen2-style), imports nothing
of the program.

Semantics, as the configuration states them:
  - weights drawn from the seed exactly as the model's initializer draws
    them (re-derived here from the same key), every projection
    quantized to ``weight_bits`` with one symmetric abs-max scale per
    output column; the tied embedding fake-quantized the same way per
    hidden column;
  - every projection's input quantized to ``act_bits`` with one
    symmetric abs-max scale per row, an integer matmul, and
    ``(acc * row_scale) * col_scale``;
  - RMSNorm, RoPE, grouped-query causal attention, SiLU-gated MLP and a
    tied logits head in ``compute_dtype``; float matmuls (attention, the
    logits head) at the configuration's ``matmul_precision``.
  - A prompt is served as one prefill over the mix's ``prompt_pad``
    positions, keys and values in the compute dtype (past
    ``blockwise_above`` positions an online softmax over key blocks of
    ``attention_block``), and stored in a slot's cache of
    ``kv_cache_dtype``; each later token is decoded in its slot of a
    batch of ``slots``, one step through every layer at a time, attending
    to the slot's cache of ``max_len`` positions up to its own, with
    probabilities and output in the cache dtype.

Every value that a later activation quantizer reads passes a rounding
boundary somewhere, and w4a4 turns any difference there into different
codes that grow layer by layer. So the reference does the same
arithmetic at the shapes, and in the program structure, serving does
it: a prompt as one padded prefill, each decode step as one scan over
the layers for a batch of ``slots`` against ``max_len`` caches. A
decode layer jitted by itself rounds some rows differently from the
same layer inside such a scan on the TPU.

The check teacher-forces each sampled request, its prompt followed by
its served tokens, and reads at every served position the gap by which
the served token's logit lies below the reference's best. Under greedy
decoding a sound program serves the reference's first choice. The
control is the same reference computed in bfloat16, put in the
program's place: at each position its first choice is read the same way.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import seeds

NEG = -1e30
# the decode step's matmul inputs are fenced as the served model's kernel
# calls fence them (see ``_qmm``): a compiled kernel is a program of its
# own, while the CPU's interpreter traces its body inline. Of "", "in",
# "out" and "in+out", this fence brings the decode step's rounding on the
# TPU closest to the served one's (bench/tools/diverge.py --fences); some
# rows still part
DECODE_FENCE = ("in",) if jax.default_backend() == "tpu" else ()


def _dims(cfg: Dict, mix: Dict) -> Dict:
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return dict(d=d, heads=heads, kv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // heads,
                ff=cfg["intermediate_size"], layers=cfg["num_hidden_layers"],
                vocab=cfg["vocab_size"],
                vocab_pad=-(-cfg["vocab_size"] // 256) * 256,
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
                wbits=cfg["pim"]["weight_bits"], abits=cfg["pim"]["act_bits"],
                kv_dtype=cfg["kv_cache_dtype"],
                block=cfg["attention_block"],
                pad=mix["prompt_pad"], max_len=mix["max_len"],
                rows=mix["slots"],
                blockwise=mix["prompt_pad"] > cfg["blockwise_above"])


def _qmax(bits: int) -> int:
    return (1 << (bits - 1)) - 1


def _quantize(x, bits: int, axis: int):
    """Symmetric abs-max codes and scale, reducing over ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-8) / _qmax(bits)
    codes = jnp.clip(jnp.round(x / scale), -_qmax(bits), _qmax(bits))
    return codes.astype(jnp.int8), scale


def _dense(key, d_in: int, d_out: int):
    return jax.random.normal(key, (d_in, d_out)) * (1.0 / math.sqrt(d_in))


@functools.partial(jax.jit, static_argnames=("dims",))
def _embedding(key, dims):
    """The tied table, fake-quantized per hidden column (straight-through
    form, as the program writes it: x + (qdq - x))."""
    dm = dict(dims)
    x = jax.random.normal(key, (dm["vocab_pad"], dm["d"])) * 0.02
    codes, scale = _quantize(x, dm["wbits"], 0)
    qdq = codes.astype(jnp.float32) * scale
    inside = (jnp.abs(x) <= scale * _qmax(dm["wbits"])).astype(x.dtype)
    return x * inside + jax.lax.stop_gradient(qdq - x * inside)


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer_weights(key, dims):
    """One layer's projections, drawn as the initializer draws them
    (attention from the first of six keys, MLP from the fifth), as codes
    and per-column scales."""
    dm = dict(dims)
    d, q, kv, ff = dm["d"], dm["heads"] * dm["hd"], dm["kv"] * dm["hd"], \
        dm["ff"]
    ks = jax.random.split(key, 6)
    ka = jax.random.split(ks[0], 4)
    k1, k2, k3 = jax.random.split(ks[4], 3)
    ws = {"q": _dense(ka[0], d, q), "k": _dense(ka[1], d, kv),
          "v": _dense(ka[2], d, kv), "o": _dense(ka[3], q, d),
          "up": _dense(k1, d, ff), "gate": _dense(k2, d, ff),
          "down": _dense(k3, ff, d)}
    return {n: _quantize(w, dm["wbits"], 0) for n, w in ws.items()}


def _qmm(x, w, bits: int, fence=()):
    """Quantized matmul over the last axis: per-row activation codes
    (scaled in the input's dtype) times the weight codes in integers,
    dequantized in float32 as (acc * row_scale) * col_scale and returned
    in the input's dtype. ``fence`` ("in", "out") keeps the compiler from
    fusing across the codes and scales going in or the dequantized result
    coming out, as a matmul kernel that is its own program does."""
    codes_w, scale_w = w
    lead = x.shape[:-1]
    codes_a, scale_a = _quantize(x.reshape(-1, x.shape[-1]), bits, -1)
    if "in" in fence:
        codes_a, scale_a = jax.lax.optimization_barrier((codes_a, scale_a))
    acc = jax.lax.dot(codes_a, codes_w, preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * scale_a.astype(jnp.float32) * scale_w
    if "out" in fence:
        out = jax.lax.optimization_barrier(out)
    return out.astype(x.dtype).reshape(lead + (codes_w.shape[1],))


def _rms(x, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps)


def _rope(x, pos, theta: float):
    """x (b, s, heads, hd) at positions pos (b, s)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[..., :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(ang)[..., :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[..., :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, mask):
    """Grouped-query attention of q (b, s, heads, hd) over k, v (b, t,
    kv, hd) where ``mask`` (b, s, t) holds: scores in float32, softmax
    probabilities in v's dtype times v. Returns (b, s, heads * hd)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, s, kvh, h // kvh, hd)
    logits = jnp.einsum("bskrd,btkd->bkrst", qg, k).astype(jnp.float32)
    logits = logits / math.sqrt(hd)
    logits = jnp.where(mask[:, None, None], logits, NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bkrst,btkd->bskrd", probs, v).reshape(b, s, h * hd)


def _attend_blockwise(q, k, v, pos, block: int):
    """The same attention as an online softmax over key blocks of
    ``block``: q pre-scaled by 1/sqrt(hd), per block the running max,
    rescaled accumulator and normalizer, divided at the end."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    nblk = s // block
    qg = (q.reshape(b, s, kvh, rep, hd) / math.sqrt(hd)).astype(q.dtype)
    kb = jnp.moveaxis(k.reshape(b, nblk, block, kvh, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nblk, block, kvh, hd), 1, 0)
    pb = jnp.moveaxis(pos.reshape(b, nblk, block), 1, 0)

    def step(carry, inp):
        acc, m_run, l_run = carry
        kc, vc, pc = inp
        logits = jnp.einsum("bskrd,btkd->bkrst", qg, kc,
                            preferred_element_type=jnp.float32)
        mask = pos[..., :, None] >= pc[..., None, :]
        logits = jnp.where(mask[:, None, None], logits, NEG)
        m_new = jnp.maximum(m_run, logits.max(axis=-1))
        scale = jnp.exp(m_run - m_new)
        p = jnp.exp(logits - m_new[..., None])
        acc = acc * scale[..., None] + jnp.einsum(
            "bkrst,btkd->bkrsd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (acc, m_new, l_run * scale + p.sum(axis=-1)), None

    acc0 = jnp.zeros((b, kvh, rep, s, hd), jnp.float32)
    m0 = jnp.full((b, kvh, rep, s), NEG, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, s), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(step, (acc0, m0, l0), (kb, vb, pb))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    out = jnp.moveaxis(out.reshape(b, kvh * rep, s, hd), 1, 2)
    return out.astype(q.dtype).reshape(b, s, h * hd)


def _mlp(x, w, dm, fence=()):
    a = _rms(x, dm["eps"])
    up = _qmm(a, w["up"], dm["abits"], fence)
    gate = _qmm(a, w["gate"], dm["abits"], fence)
    return x + _qmm(jax.nn.silu(gate) * up, w["down"], dm["abits"], fence)


def _qkv(x, w, pos, dm, fence=()):
    b, s = x.shape[:2]
    h, kvh, hd = dm["heads"], dm["kv"], dm["hd"]
    a = _rms(x, dm["eps"])
    q = _rope(_qmm(a, w["q"], dm["abits"], fence).reshape(b, s, h, hd), pos,
              dm["theta"])
    k = _rope(_qmm(a, w["k"], dm["abits"], fence).reshape(b, s, kvh, hd),
              pos, dm["theta"])
    v = _qmm(a, w["v"], dm["abits"], fence).reshape(b, s, kvh, hd)
    return q, k, v


@functools.partial(jax.jit, static_argnames=("dims",))
def _prefill_layer(x, w, dims):
    """One layer over one padded prompt x (1, pad, d). Returns the
    layer's output and the prompt's keys and values in the cache dtype
    (pad, kv, hd)."""
    dm = dict(dims)
    s = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (1, s))
    q, k, v = _qkv(x, w, pos, dm)
    if dm["blockwise"]:
        att = _attend_blockwise(q, k, v, pos, dm["block"])
    else:
        att = _attend(q, k, v, pos[:, :, None] >= pos[:, None, :])
    x = x + _qmm(att, w["o"], dm["abits"])
    kv_dt = getattr(jnp, dm["kv_dtype"])
    return _mlp(x, w, dm), k[0].astype(kv_dt), v[0].astype(kv_dt)


@functools.partial(jax.jit, static_argnames=("dims",))
def _slots(kps, dims):
    """The slots' caches of one layer (rows, max_len, kv, hd): slot j
    holds request j's prompt keys or values (pad, kv, hd) from row 0."""
    dm = dict(dims)
    c = jnp.zeros((dm["rows"], dm["max_len"]) + kps[0].shape[1:],
                  kps[0].dtype)
    for j, kp in enumerate(kps):
        c = c.at[j, :kp.shape[0]].set(kp)
    return c


@functools.partial(jax.jit, static_argnames=("dims", "fence"),
                   donate_argnums=(2, 3))
def _decode_step(table, tokens, ck, cv, w, pos, dims, fence):
    """One decode step of a batch of slots through every layer, as a scan
    over the layers: each slot's token (rows,) at its position (rows,),
    its new key and value written into its cache ck, cv (layers, rows,
    max_len, kv, hd) at that position, its query attending to the cache
    up to it. Returns the logits (rows, vocab) and the caches."""
    dm = dict(dims)
    x = jnp.take(table, tokens[:, None], axis=0)
    kpos = jnp.arange(ck.shape[2], dtype=jnp.int32)
    valid = kpos[None, None, :] <= pos[:, None, None]
    put = lambda c, new, i: jax.lax.dynamic_update_slice(c, new, (i, 0, 0))

    def layer(x, inp):
        w, kc, vc = inp
        q, k, v = _qkv(x, w, pos[:, None], dm, fence)
        kc = jax.vmap(put)(kc, k.astype(kc.dtype), pos)
        vc = jax.vmap(put)(vc, v.astype(vc.dtype), pos)
        x = x + _qmm(_attend(q, kc, vc, valid), w["o"], dm["abits"], fence)
        return _mlp(x, w, dm, fence), (kc, vc)

    x, (ck, cv) = jax.lax.scan(layer, x, (w, ck, cv))
    y = _rms(x, dm["eps"])
    logits = (y @ table.T)[:, 0, :dm["vocab"]].astype(jnp.float32)
    return logits, ck, cv


@functools.partial(jax.jit, static_argnames=("dims",))
def _logits(x, table, dims):
    dm = dict(dims)
    y = _rms(x, dm["eps"])
    return (y @ table.astype(y.dtype).T)[..., :dm["vocab"]].astype(
        jnp.float32)


def forward(cfg: Dict, mix: Dict, seed: int, samples: List[Dict],
            dtype, on_layer: Optional[Callable] = None,
            fence: Tuple[str, ...] = DECODE_FENCE):
    """Teacher-forced pass of every sampled request in ``dtype``, laid out
    as serving runs it: each prompt as one prefill padded to ``pad``
    positions, then request j in slot j of a batch of ``rows`` slots,
    decoded one step at a time from its served tokens. Returns, per
    request, the logits (float32) at every served position: row i scores
    served token i. ``on_layer(layer, request, k, v)`` sees each layer's
    cache of each request after the last step."""
    rows = mix["slots"]
    out = []
    for g in range(0, len(samples), rows):
        cb = None if on_layer is None else (
            lambda i, j, k, v, g=g: on_layer(i, g + j, k, v))
        out += _forward_slots(cfg, mix, seed, samples[g:g + rows], dtype, cb,
                              fence)
    return out


def _forward_slots(cfg: Dict, mix: Dict, seed: int, samples: List[Dict],
                   dtype, on_layer: Optional[Callable], fence):
    """``forward`` for as many requests as there are slots."""
    dm = _dims(cfg, mix)
    dims = tuple(sorted(dm.items()))
    rows, layers = dm["rows"], dm["layers"]
    key = seeds.jax_key(seed, seeds.WEIGHTS)
    ks = jax.random.split(key, 8)
    layer_keys = jax.random.split(ks[1], layers)
    prompts = [np.asarray(s["prompt"]) for s in samples]
    served = [np.asarray(s["tokens"]) for s in samples]
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        table = _embedding(ks[0], dims).astype(dtype)
        xp = []
        for p in prompts:
            ids = np.zeros((dm["pad"],), np.int32)
            ids[:len(p)] = p
            xp.append(table[jnp.asarray(ids)][None])
        ws, kcs, vcs = [], [], []
        for i in range(layers):
            w = _layer_weights(layer_keys[i], dims)
            kps, vps = [], []
            for j in range(len(samples)):
                xp[j], kp, vp = _prefill_layer(xp[j], w, dims)
                kps.append(kp)
                vps.append(vp)
            kcs.append(_slots(kps, dims))
            vcs.append(_slots(vps, dims))
            ws.append(w)
        w = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ws)
        ck, cv = jnp.stack(kcs), jnp.stack(vcs)
        del ws, kcs, vcs
        out = [[np.asarray(_logits(x[:, len(p) - 1:len(p)], table,
                                   dims)[0, 0])]
               for x, p in zip(xp, prompts)]
        del xp
        # slots with nothing to decode write at the last position, which
        # no served token's key reaches
        for t in range(max(len(s) for s in served) - 1):
            live = [j for j, s in enumerate(served) if t < len(s) - 1]
            tokens = np.zeros((rows,), np.int32)
            pos = np.full((rows,), dm["max_len"] - 1, np.int32)
            for j in live:
                tokens[j], pos[j] = served[j][t], len(prompts[j]) + t
            lg, ck, cv = _decode_step(table, jnp.asarray(tokens), ck, cv, w,
                                      jnp.asarray(pos), dims, fence)
            lg = np.asarray(lg[:len(samples)])
            for j in live:
                out[j].append(lg[j])
        if on_layer is not None:
            for i in range(layers):
                for j in range(len(samples)):
                    on_layer(i, j, ck[i, j], cv[i, j])
    return [np.stack(o) for o in out]


def check(cfg: Dict, mix: Dict, seed: int, samples: List[Dict],
          control: bool = False) -> Dict[str, float]:
    """Readings over the sampled requests: ``token_gap``, the widest gap
    of a served token below the reference's best logit, with its mean,
    the share of served tokens that are not the reference's first
    choice, and the widest gap of the first served tokens (prefill's).
    With ``control``, the same readings of the bfloat16 reference's first
    choices (``control_*``)."""
    if not samples:
        return {}
    ref = forward(cfg, mix, seed, samples, jnp.float32)
    picks = {"": [np.asarray(s["tokens"]) for s in samples]}
    if control:
        ctl = forward(cfg, mix, seed, samples, jnp.bfloat16)
        picks["control_"] = [np.argmax(c, axis=-1) for c in ctl]
    out: Dict[str, float] = {}
    for pre, toks in picks.items():
        gaps, firsts, miss = [], [], 0
        for lg, tok in zip(ref, toks):
            best = lg.max(axis=-1)
            gap = best - np.take_along_axis(lg, tok[:, None], 1)[:, 0]
            gaps.extend(gap.tolist())
            firsts.append(float(gap[0]))
            miss += int(np.sum(tok != np.argmax(lg, axis=-1)))
        out[pre + "token_gap"] = float(max(gaps))
        out[pre + "token_gap_mean"] = float(np.mean(gaps))
        out[pre + "token_miss_share"] = miss / len(gaps)
        # the first served token comes from prefill, the rest from decode
        out[pre + "first_token_gap"] = float(max(firsts))
    out["served_tokens_checked"] = sum(len(t) for t in picks[""])
    return out
