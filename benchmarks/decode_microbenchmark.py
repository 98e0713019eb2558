"""Per-phase serving-engine microbenchmark (maxtext-style).

Times each verb of the :class:`repro.serving.ServingEngine` facade in
isolation — prefill, insert, generate — across slot-pool sizes, then
measures the two production semantics this engine exists for:

  * chunked prefill: on a mixed burst with a long prompt, the metric
    that matters is the *token stall* — the longest wall-clock gap in
    token delivery across all running slots. Unchunked, the monolithic
    long prefill freezes every in-flight request for its whole duration;
    chunked, decode steps interleave between chunks and the stall
    collapses to roughly one chunk. (Virtual-clock TTFT is scheduling
    policy and intentionally identical; the wall-clock marks are what
    the chunk size buys.)
  * shared-prefix KV reuse: sweep the fraction of requests sharing a
    long system prompt and report cache hit rate, prefill work units,
    and wall-clock TTFT — hits skip the shared prefix entirely, so TTFT
    drops as the share fraction rises.

  PYTHONPATH=src python benchmarks/decode_microbenchmark.py
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, Hashable, List, Tuple

import jax
import numpy as np

Row = Tuple[str, float, str]

# big enough that a decode step outweighs host scheduling on CPU, small
# enough to stay a microbenchmark (same regime as serving_bench)
D_MODEL, NUM_LAYERS, VOCAB = 256, 4, 256
SLOT_SWEEP = (2, 4, 8)
GEN = 24
LONG_PROMPT, SHORT_PROMPT = 64, 8
CHUNK = 8


def _build():
    from repro.configs.base import get_config
    from repro.models.lm import init_lm
    cfg = get_config("qwen2.5-3b").reduced(num_layers=NUM_LAYERS,
                                           d_model=D_MODEL, vocab=VOCAB)
    return cfg, init_lm(cfg, jax.random.PRNGKey(0))


def _pct(vals, q):
    return float(np.percentile(np.asarray(vals, np.float64), q))


# ---------------------------------------------------------------------------
# phase timing: prefill / insert / generate, per slot-pool size
# ---------------------------------------------------------------------------
def phase_bench(cfg, params) -> List[Row]:
    from repro.serving import ServingEngine
    rows: List[Row] = []
    rng = np.random.default_rng(0)
    for slots in SLOT_SWEEP:
        eng = ServingEngine(params, cfg, num_slots=slots, prompt_pad=32,
                            max_len=32 + GEN)
        eng.warmup()
        prompts = [rng.integers(0, VOCAB, size=(32,)).astype(np.int32)
                   for _ in range(slots)]
        t0 = time.perf_counter()
        prefixes = [eng.prefill(p) for p in prompts]
        t_prefill = time.perf_counter() - t0
        state = eng.init_state()
        t0 = time.perf_counter()
        views = []
        for i, pre in enumerate(prefixes):
            state, v = eng.insert(pre, state, max_new_tokens=GEN,
                                  request_id=i)
            views.append(v)
        jax.block_until_ready(state.cache)
        t_insert = time.perf_counter() - t0
        t0 = time.perf_counter()
        steps = 0
        while state.slots:
            state, res = eng.generate(state)
            steps += res.steps
        t_gen = time.perf_counter() - t0
        toks = sum(len(v.tokens) for v in views)
        rows += [
            (f"engine_phase.slots{slots}.prefill.us_per_call",
             t_prefill / slots * 1e6, "one padded prompt through the "
             "model (host-synced first token)"),
            (f"engine_phase.slots{slots}.insert.us_per_call",
             t_insert / slots * 1e6, "masked KV scatter into a slot row"),
            (f"engine_phase.slots{slots}.generate.us_per_step",
             t_gen / steps * 1e6, f"{steps} fused all-slot decode steps"),
            (f"engine_phase.slots{slots}.decode.tokens_per_s",
             toks / t_gen, f"{toks} tokens across {slots} slots"),
        ]
    return rows


# ---------------------------------------------------------------------------
# chunked vs unchunked prefill: token-stall + wall TTFT on a mixed burst
# ---------------------------------------------------------------------------
class _WallMarks:
    """Callback recording a wall timestamp per delivered token, and each
    request's first and last token times from the callback's creation
    (just before the run)."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.marks: List[float] = []
        self.first: Dict[Hashable, float] = {}
        self.last: Dict[Hashable, float] = {}

    def on_admit(self, request_id, slot, step):
        pass

    def on_token(self, request_id, token, index):
        t = time.perf_counter()
        self.marks.append(t)
        self.first.setdefault(request_id, t - self.t0)
        self.last[request_id] = t - self.t0

    def on_finish(self, completion):
        pass

    def max_gap_ms(self) -> float:
        gaps = np.diff(np.asarray(self.marks))
        return float(gaps.max() * 1e3) if gaps.size else 0.0


def _mixed_burst(rng) -> list:
    from repro.serving import Request
    reqs = [Request(f"s{i}", rng.integers(
        0, VOCAB, size=(SHORT_PROMPT,)).astype(np.int32),
        max_new_tokens=GEN, arrival=0.0) for i in range(4)]
    reqs.append(Request("long", rng.integers(
        0, VOCAB, size=(LONG_PROMPT,)).astype(np.int32),
        max_new_tokens=8, arrival=1.0))
    reqs += [Request(f"t{i}", rng.integers(
        0, VOCAB, size=(SHORT_PROMPT,)).astype(np.int32),
        max_new_tokens=12, arrival=3.0 + i) for i in range(3)]
    return reqs


def chunked_prefill_bench(cfg, params) -> List[Row]:
    from repro.serving import ContinuousScheduler
    rows: List[Row] = []
    rng = np.random.default_rng(1)
    reqs = _mixed_burst(rng)
    for label, chunk in (("unchunked", None), (f"chunk{CHUNK}", CHUNK)):
        sched = ContinuousScheduler(
            params, cfg, num_slots=4, prompt_pad=LONG_PROMPT,
            max_len=LONG_PROMPT + GEN, prefill_chunk=chunk)
        sched.warmup()
        sched.run(reqs)                      # warm second-call paths
        cb = _WallMarks()
        res = sched.run(reqs, callbacks=cb)
        ttfts = [t * 1e3 for t in cb.first.values()]
        fins = [t * 1e3 for t in cb.last.values()]
        rows += [
            (f"engine_chunked.{label}.max_token_stall_ms",
             cb.max_gap_ms(), "longest wall gap in token delivery "
             "(the long prompt's prefill shadow)"),
            (f"engine_chunked.{label}.ttft_wall_ms_p90",
             _pct(ttfts, 90), "wall time to first token, p90"),
            (f"engine_chunked.{label}.finish_wall_ms_p90",
             _pct(fins, 90), "wall time to completion, p90"),
            (f"engine_chunked.{label}.prefill_units",
             float(res.metrics["prefill_units"]),
             "compiled prefill calls across the run"),
        ]
    return rows


# ---------------------------------------------------------------------------
# prefix-cache hit-rate sweep
# ---------------------------------------------------------------------------
def prefix_cache_bench(cfg, params) -> List[Row]:
    from repro.serving import ContinuousScheduler, Request
    rows: List[Row] = []
    rng = np.random.default_rng(2)
    m = 48                                    # shared system prompt
    shared = rng.integers(0, VOCAB, size=(m,)).astype(np.int32)
    n = 8
    for frac in (0.0, 0.5, 1.0):
        reqs = []
        for i in range(n):
            tail = rng.integers(0, VOCAB,
                                size=(SHORT_PROMPT,)).astype(np.int32)
            if i < int(frac * n):
                reqs.append(Request(i, np.concatenate([shared, tail]),
                                    max_new_tokens=8, arrival=0.0,
                                    shared_prefix_len=m))
            else:
                full = rng.integers(0, VOCAB, size=(
                    m + SHORT_PROMPT,)).astype(np.int32)
                reqs.append(Request(i, full, max_new_tokens=8,
                                    arrival=0.0))
        sched = ContinuousScheduler(
            params, cfg, num_slots=4, prompt_pad=m + SHORT_PROMPT,
            max_len=m + SHORT_PROMPT + 8, prefill_chunk=CHUNK,
            prefix_cache=16)
        sched.warmup()
        cb = _WallMarks()
        res = sched.run(reqs, callbacks=cb)
        stats = res.metrics["prefix_cache"]
        total = stats["hits"] + stats["misses"]
        ttfts = [t * 1e3 for t in cb.first.values()]
        tag = f"engine_prefix.share{int(frac * 100):03d}"
        rows += [
            (f"{tag}.hit_rate", stats["hits"] / total if total else 0.0,
             f"{stats['hits']}/{total} lookups hit"),
            (f"{tag}.prefill_units",
             float(res.metrics["prefill_units"]),
             "compiled prefill calls (hits skip the shared prefix)"),
            (f"{tag}.ttft_wall_ms_p50", _pct(ttfts, 50),
             "wall time to first token, p50"),
        ]
    return rows


def all_rows() -> List[Row]:
    cfg, params = _build()
    return (phase_bench(cfg, params) + chunked_prefill_bench(cfg, params)
            + prefix_cache_bench(cfg, params))


def main() -> None:
    argparse.ArgumentParser().parse_args()
    print("name,value,derived")
    for name, value, derived in all_rows():
        print(f"{name},{value:.6g},{derived}")


if __name__ == "__main__":
    main()
