"""Operations and bytes of the kernels' logical calls, from shapes alone.

A ``pim_matmul`` call multiplies an (M, K) activation block by a
programmed (K, N) weight at the configured bit widths. Its operations
are 2·M·K·N; its bytes are the activation codes and weight codes at
their bit widths, the float32 output, and the float32 row and column
scales. Neither count depends on how many nibble planes or how much tile
padding the kernel uses, so the same work is counted whatever the
implementation does.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

Call = Tuple[int, int, int, int]          # (M, K, N, number of calls)


def matmul_ops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def matmul_bytes(m: int, k: int, n: int, weight_bits: int,
                 act_bits: int) -> int:
    codes = -(-m * k * act_bits // 8) + -(-k * n * weight_bits // 8)
    return codes + 4 * m * n + 4 * m + 4 * n


def least_time_s(calls: Iterable[Call], peaks: Dict, weight_bits: int,
                 act_bits: int) -> Tuple[float, str]:
    """The least time the chip could take for ``calls``: per call the
    larger of operations over the int8 peak and bytes over HBM
    bandwidth, summed. Also says which bound held for most of it."""
    t = {"compute": 0.0, "memory": 0.0}
    for m, k, n, count in calls:
        tc = matmul_ops(m, k, n) / peaks["int8_ops_per_s"]
        tm = matmul_bytes(m, k, n, weight_bits, act_bits) / \
            peaks["hbm_bytes_per_s"]
        t["compute" if tc >= tm else "memory"] += count * max(tc, tm)
    return t["compute"] + t["memory"], max(t, key=t.get)


# ---------------------------------------------------------------------------
# decoder LM (attention + gated MLP, every projection programmed)
# ---------------------------------------------------------------------------
def lm_projections(cfg: Dict) -> List[Tuple[int, int]]:
    """(K, N) of each programmed projection of one decoder layer: q, k,
    v, o, up, gate, down."""
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    ff = cfg["intermediate_size"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (d, ff), (ff, d)]


def lm_calls(cfg: Dict, rows: int, times: int) -> List[Call]:
    """Logical kernel calls of ``times`` forward passes over ``rows``
    rows each (a decode step: rows = slots; a prefill: rows = the padded
    prompt)."""
    layers = cfg["num_hidden_layers"]
    return [(rows, k, n, layers * times) for k, n in lm_projections(cfg)]


def lm_int_ops_per_token(cfg: Dict) -> int:
    """Integer operations of the programmed projections per token."""
    return cfg["num_hidden_layers"] * sum(
        matmul_ops(1, k, n) for k, n in lm_projections(cfg))


# ---------------------------------------------------------------------------
# ResNet (basic blocks), every conv an im2col GEMM and the head a dense
# ---------------------------------------------------------------------------
def resnet_layers(cfg: Dict) -> List[Dict]:
    """Every conv and dense layer of a ResNet of basic blocks, in the
    order the initializer draws them: a stem conv; per block c1 (strided
    in a stage's first block), c2, and a 1x1 shortcut conv ``ds`` where
    the shape changes; a dense head after global average pooling."""
    k, hw = cfg["kernel_size"], cfg["image_size"]
    c = cfg["stem_channels"]
    out = [dict(kind="conv", name="stem", hw=hw, cin=3, cout=c, k=k,
                stride=1)]
    for s, (ch, blocks, stride0) in enumerate(cfg["stages"]):
        for b in range(blocks):
            st = stride0 if b == 0 else 1
            out.append(dict(kind="conv", name=f"s{s}b{b}c1", hw=hw, cin=c,
                            cout=ch, k=k, stride=st))
            hw2 = -(-hw // st)
            out.append(dict(kind="conv", name=f"s{s}b{b}c2", hw=hw2, cin=ch,
                            cout=ch, k=k, stride=1))
            if st != 1 or c != ch:
                out.append(dict(kind="conv", name=f"s{s}b{b}ds", hw=hw,
                                cin=c, cout=ch, k=1, stride=st))
            hw, c = hw2, ch
    out.append(dict(kind="dense", name="fc", cin=c, cout=cfg["num_classes"]))
    return out


def cnn_calls(cfg: Dict, batch: int, times: int) -> List[Call]:
    """Logical kernel calls of ``times`` forward passes over ``batch``
    images: a conv's rows are its output pixels, its K the patch."""
    calls = []
    for l in resnet_layers(cfg):
        if l["kind"] == "conv":
            oh = -(-l["hw"] // l["stride"])
            calls.append((batch * oh * oh, l["k"] * l["k"] * l["cin"],
                          l["cout"], times))
        else:
            calls.append((batch, l["cin"], l["cout"], times))
    return calls


def cnn_int_ops_per_image(cfg: Dict) -> int:
    return sum(matmul_ops(m, k, n) for m, k, n, _ in cnn_calls(cfg, 1, 1))


def roofline_share(run, kernel: str):
    """A kernel's share of its roofline over a traced window, in %: the
    least time of the window's logical calls over the kernel's summed
    device time. None where the trace holds no such kernel."""
    t = run.trace.kernel_s.get(kernel) if run.trace else None
    if not t or not run.kernel_calls:
        return None
    pim = run.config["pim"]
    least, _ = least_time_s(run.kernel_calls, run.peaks, pim["weight_bits"],
                            pim["act_bits"])
    return 100.0 * least / t
