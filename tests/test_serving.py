"""Continuous-batching serving subsystem: slot-allocator invariants,
hypothesis-driven scheduler properties (random arrivals/lengths -> no
slot leaks, every request completes exactly once, tokens identical to a
static run), exact-pallas token parity, compile-once step functions, and
the structured metrics dump."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from hypo_compat import given, settings, st  # noqa: E402

from repro.configs.base import get_config
from repro.core.pim import PimConfig
from repro.models import attention as attn
from repro.models.lm import init_cache, init_lm
from repro.serving import (ContinuousScheduler, Request, SlotAllocator,
                           TokenCollector, poisson_trace, static_generate)
from repro.serving.slots import check_slot_compatible


def _small_cfg(arch="qwen2.5-3b", layers=2, d_model=64, vocab=128):
    return get_config(arch).reduced(num_layers=layers, d_model=d_model,
                                    vocab=vocab)


# ---------------------------------------------------------------------------
# slot allocator
# ---------------------------------------------------------------------------
def test_allocator_alloc_free_cycle():
    al = SlotAllocator(3)
    slots = [al.alloc(f"r{i}") for i in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert al.alloc("r3") is None, "exhausted pool must refuse"
    assert al.num_free == 0 and al.num_active == 3
    al.free(slots[1])
    assert al.num_free == 1
    assert al.alloc("r4") == slots[1], "freed slot is immediately reusable"
    for s in (slots[0], slots[1], slots[2]):
        al.free(s)
    assert al.num_active == 0 and al.num_free == 3


def test_allocator_double_free_raises():
    al = SlotAllocator(2)
    s = al.alloc("r0")
    al.free(s)
    with pytest.raises(ValueError):
        al.free(s)
    with pytest.raises(ValueError):
        SlotAllocator(0)


def test_slot_compat_rejects_stateful_archs():
    with pytest.raises(NotImplementedError):
        check_slot_compatible(_small_cfg("mamba2-370m"))
    with pytest.raises(NotImplementedError):
        check_slot_compatible(_small_cfg("whisper-medium"))
    check_slot_compatible(_small_cfg())  # attention-only passes


# ---------------------------------------------------------------------------
# KV-cache construction dedup
# ---------------------------------------------------------------------------
def test_init_cache_built_on_init_kv_cache():
    """lm.init_cache and attention.init_kv_cache share one geometry: the
    layered KV arrays are exactly init_kv_cache with layers= set."""
    cfg = _small_cfg()
    cache = init_cache(cfg, batch=3, max_len=10)
    layered = attn.init_kv_cache(3, 10, cfg.num_kv_heads, cfg.head_dim,
                                 layers=cfg.num_layers)
    assert cache["k"].shape == layered["k"].shape == (
        cfg.num_layers, 3, 10, cfg.num_kv_heads, cfg.head_dim)
    assert cache["v"].dtype == layered["v"].dtype
    per_layer = attn.init_kv_cache(3, 10, cfg.num_kv_heads, cfg.head_dim)
    assert per_layer["k"].shape == (3, 10, cfg.num_kv_heads, cfg.head_dim)


# ---------------------------------------------------------------------------
# scheduler invariants (hypothesis-driven)
# ---------------------------------------------------------------------------
@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000))
def test_scheduler_invariants_random_traffic(seed):
    """Random arrivals and lengths: every request completes exactly once,
    no slot leaks, and every decoded token equals a straight static-batch
    run of the same request."""
    cfg = _small_cfg()
    params = _PARAMS_CACHE.setdefault(
        "plain", init_lm(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 8))
    rate = float(rng.choice([0.0, 0.3, 1.5]))
    reqs = poisson_trace(n=n, rate=rate,
                         prompt_lens=[1, 2, 5, 8, 12],
                         gen_lens=[1, 2, 4, 7],
                         vocab=cfg.vocab_size, seed=seed)
    sched = _SCHED_CACHE.setdefault(
        "plain", ContinuousScheduler(params, cfg, num_slots=2,
                                     prompt_pad=12, max_len=19))
    col = TokenCollector()
    res = sched.run(reqs, callbacks=col)
    assert len(res.completions) == len(reqs)
    ids = [c.request_id for c in res.completions]
    assert sorted(ids) == sorted(r.request_id for r in reqs), \
        "every request completes exactly once"
    by_id = res.tokens_by_id()
    for r in reqs:
        got = by_id[r.request_id]
        assert got.shape == (r.max_new_tokens,)
        ref = static_generate(params, cfg, r.tokens, r.max_new_tokens)
        np.testing.assert_array_equal(got, ref)
        # streamed tokens agree with the completion record
        assert col.streamed[r.request_id] == got.tolist()


# module-level caches so the hypothesis loop reuses one compiled scheduler
_PARAMS_CACHE = {}
_SCHED_CACHE = {}


@pytest.mark.parametrize("sync_every", [2, 4])
def test_sync_every_token_equality(sync_every):
    """Fused multi-step decode windows (sync_every > 1) produce exactly
    the tokens and latency accounting of single-step decoding — only the
    host-sync cadence changes (fewer syncs than decode steps on a burst),
    and the step functions stay compile-once per shape."""
    cfg = _small_cfg()
    params = _PARAMS_CACHE.setdefault(
        "plain", init_lm(cfg, jax.random.PRNGKey(0)))
    # burst + mixed lengths: exercises full windows, ragged tails, and
    # admission interleaving
    reqs = poisson_trace(n=10, rate=0.0, prompt_lens=[2, 5, 8, 12],
                         gen_lens=[1, 3, 8, 13], vocab=cfg.vocab_size,
                         seed=11)
    reqs += poisson_trace(n=4, rate=0.5, prompt_lens=[3, 6],
                          gen_lens=[4, 9], vocab=cfg.vocab_size, seed=12)
    for i, r in enumerate(reqs):
        r.request_id = i
    base = ContinuousScheduler(params, cfg, num_slots=3, prompt_pad=12,
                               max_len=25)
    fused = ContinuousScheduler(params, cfg, num_slots=3, prompt_pad=12,
                                max_len=25, sync_every=sync_every)
    r0, r1 = base.run(reqs), fused.run(reqs)
    t0, t1 = r0.tokens_by_id(), r1.tokens_by_id()
    for rid in t0:
        np.testing.assert_array_equal(t0[rid], t1[rid])
    assert r1.metrics["decode_steps"] == r0.metrics["decode_steps"]
    assert r1.metrics["host_syncs"] < r0.metrics["host_syncs"]
    assert r1.metrics["sync_every"] == sync_every
    for k in r0.metrics:
        if "ttft" in k or "latency" in k:
            assert r0.metrics[k] == r1.metrics[k], k
    # one single-step trace + one window trace, regardless of traffic
    assert fused.decode_traces <= 2


def test_sync_every_validation():
    cfg = _small_cfg()
    params = _PARAMS_CACHE.setdefault(
        "plain", init_lm(cfg, jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="sync_every"):
        ContinuousScheduler(params, cfg, num_slots=2, prompt_pad=8,
                            max_len=16, sync_every=0)


def test_scheduler_latency_accounting():
    """TTFT/latency bookkeeping: a request that arrives late cannot be
    admitted before it arrives, and metrics cover every completion."""
    cfg = _small_cfg()
    params = _PARAMS_CACHE.setdefault(
        "plain", init_lm(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    reqs = [
        Request("early", rng.integers(0, 128, size=(4,)).astype(np.int32),
                max_new_tokens=3, arrival=0.0),
        Request("late", rng.integers(0, 128, size=(4,)).astype(np.int32),
                max_new_tokens=2, arrival=5.0),
    ]
    sched = _SCHED_CACHE.setdefault(
        "plain", ContinuousScheduler(params, cfg, num_slots=2,
                                     prompt_pad=12, max_len=19))
    res = sched.run(reqs)
    by_id = {c.request_id: c for c in res.completions}
    assert by_id["late"].admit_step > 5.0
    for c in res.completions:
        assert c.ttft_steps >= 1.0, "prefill itself costs a step"
        assert c.latency_steps >= c.ttft_steps
    m = res.metrics
    assert m["num_requests"] == 2
    assert m["generated_tokens"] == 5
    assert m["latency_steps_p90"] >= m["latency_steps_p50"] > 0


def test_scheduler_rejects_oversized_and_duplicate_requests():
    cfg = _small_cfg()
    params = _PARAMS_CACHE.setdefault(
        "plain", init_lm(cfg, jax.random.PRNGKey(0)))
    sched = ContinuousScheduler(params, cfg, num_slots=1, prompt_pad=4,
                                max_len=8)
    toks = np.arange(3, dtype=np.int32)
    with pytest.raises(ValueError, match="prompt length"):
        sched.run([Request("a", np.arange(5, dtype=np.int32), 1)])
    with pytest.raises(ValueError, match="max_len"):
        sched.run([Request("a", toks, 9)])
    with pytest.raises(ValueError, match="duplicate"):
        sched.run([Request("a", toks, 1), Request("a", toks, 1)])
    with pytest.raises(ValueError):
        ContinuousScheduler(params, cfg, num_slots=2, prompt_pad=9,
                            max_len=8)


# ---------------------------------------------------------------------------
# token parity on the real engine + compile-once
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("substrate", ["exact-pallas", "exact-jnp"])
def test_continuous_token_parity_on_engine(substrate):
    """Acceptance: continuous-batching decode over programmed plans is
    bit-identical to static prefill+decode_step over the *same* plans —
    slot refills, padded prefill, and per-slot offsets change nothing."""
    from repro.launch.serve import plan_params_for_pim
    cfg = _small_cfg(layers=1, d_model=32)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    planned = plan_params_for_pim(
        params, PimConfig(weight_bits=4, act_bits=4, substrate=substrate))
    reqs = poisson_trace(n=5, rate=0.8, prompt_lens=[2, 4, 7],
                         gen_lens=[1, 3, 5], vocab=cfg.vocab_size, seed=3)
    sched = ContinuousScheduler(planned, cfg, num_slots=2, prompt_pad=8,
                                max_len=13)
    res = sched.run(reqs)
    by_id = res.tokens_by_id()
    for r in reqs:
        ref = static_generate(planned, cfg, r.tokens, r.max_new_tokens)
        np.testing.assert_array_equal(by_id[r.request_id], ref)


def test_step_functions_compile_once_across_refills():
    """More requests than slots forces refills at heterogeneous lengths;
    prefill and decode must each trace exactly once, and stay compiled
    across a second run."""
    cfg = _small_cfg()
    params = _PARAMS_CACHE.setdefault(
        "plain", init_lm(cfg, jax.random.PRNGKey(0)))
    sched = ContinuousScheduler(params, cfg, num_slots=2, prompt_pad=12,
                                max_len=19)
    reqs = poisson_trace(n=6, rate=0.0, prompt_lens=[1, 3, 6, 9, 12],
                         gen_lens=[1, 2, 5, 7], vocab=cfg.vocab_size,
                         seed=11)
    res = sched.run(reqs)
    assert res.metrics["prefills"] == 6
    assert res.metrics["prefill_traces"] == 1
    assert res.metrics["decode_traces"] == 1
    sched.run(reqs)
    assert sched.prefill_traces == 1 and sched.decode_traces == 1


# ---------------------------------------------------------------------------
# serve driver integration + metrics json
# ---------------------------------------------------------------------------
def test_serve_continuous_driver(tmp_path):
    from repro.launch.serve import serve_continuous
    path = tmp_path / "metrics.json"
    res = serve_continuous("qwen2.5-3b", num_slots=2, num_requests=4,
                           prompt_len=8, gen=4, layers=1, d_model=32,
                           pim=True, pim_substrate="exact-jnp",
                           arrival_rate=0.5, seed=0,
                           metrics_json=str(path))
    assert res["mode"] == "continuous"
    assert res["num_requests"] == 4
    assert res["pim_substrate"] == "exact-jnp"
    assert res["opima_tokens_per_s"] > 0
    data = json.loads(path.read_text())
    for key in ("tokens_per_s", "ttft_steps_p50", "latency_steps_p99",
                "decode_traces", "requests", "opima_tokens_per_s"):
        assert key in data, f"metrics json missing {key}"
    assert len(data["requests"]) == 4
    assert all(isinstance(r["tokens"], list) for r in data["requests"])


@pytest.mark.parametrize("d_model", [None, 32], ids=["depth-only", "smoke"])
def test_setup_layers_cut(monkeypatch, d_model):
    """``layers`` alone cuts depth and keeps every published width and
    the full vocabulary; with ``d_model`` it is the smoke reduction. The
    config is read before init, so no full-width weights are built."""
    from repro.launch import serve as serve_mod

    class Built(Exception):
        pass

    def capture(cfg, key):
        raise Built(cfg)

    monkeypatch.setattr(serve_mod, "init_lm", capture)
    with pytest.raises(Built) as exc:
        serve_mod._setup("qwen2.5-3b", 3, d_model, pim=False, pim_bits=4,
                         pim_substrate=None, plan_dir=None)
    cfg, full = exc.value.args[0], get_config("qwen2.5-3b")
    assert cfg.num_layers == 3
    if d_model is None:
        assert cfg == full.with_depth(3)
        assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                cfg.d_ff, cfg.vocab_size) == (2048, 16, 2, 128, 11008,
                                              151936)
    else:
        assert (cfg.d_model, cfg.vocab_size) == (32, 512)


def test_serve_static_metrics_json(tmp_path):
    from repro.launch.serve import serve
    path = tmp_path / "static.json"
    res = serve("qwen2.5-3b", batch=1, prompt_len=6, gen=2, layers=1,
                d_model=32, metrics_json=str(path))
    data = json.loads(path.read_text())
    assert data["mode"] == "static"
    assert data["generated_tokens"] == 2
    assert data["generated"] == np.asarray(res["generated"]).tolist()


def test_warmup_compiles_once_and_preserves_tokens():
    """warmup() pre-compiles both step functions (so metered runs exclude
    compile time) without affecting the tokens a later run produces."""
    cfg = _small_cfg()
    params = _PARAMS_CACHE.setdefault(
        "plain", init_lm(cfg, jax.random.PRNGKey(0)))
    sched = ContinuousScheduler(params, cfg, num_slots=2, prompt_pad=12,
                                max_len=19)
    sched.warmup()
    assert sched.prefill_traces == 1 and sched.decode_traces == 1
    reqs = poisson_trace(n=3, rate=0.5, prompt_lens=[3, 6], gen_lens=[2, 4],
                         vocab=cfg.vocab_size, seed=7)
    res = sched.run(reqs)
    assert res.metrics["prefill_traces"] == 1
    assert res.metrics["decode_traces"] == 1
    for r in reqs:
        ref = static_generate(params, cfg, r.tokens, r.max_new_tokens)
        np.testing.assert_array_equal(res.tokens_by_id()[r.request_id], ref)


def test_trace_file_rejects_malformed_records(tmp_path):
    from repro.launch.serve import serve_continuous
    tf = tmp_path / "bad.json"
    tf.write_text(json.dumps([{"arrival": 0.0, "prompt_len": 3}]))
    with pytest.raises(ValueError, match="missing 'gen'"):
        serve_continuous("qwen2.5-3b", layers=1, d_model=32,
                         trace_file=str(tf))
    tf.write_text(json.dumps([{"arrival": 0.0, "gen": 2}]))
    with pytest.raises(ValueError, match="'tokens' or 'prompt_len'"):
        serve_continuous("qwen2.5-3b", layers=1, d_model=32,
                         trace_file=str(tf))


def test_trace_file_driven_arrivals(tmp_path):
    from repro.launch.serve import serve_continuous
    trace = [{"arrival": 0.0, "prompt_len": 3, "gen": 2},
             {"arrival": 1.5, "tokens": [5, 6, 7, 8], "gen": 1,
              "id": "explicit"}]
    tf = tmp_path / "trace.json"
    tf.write_text(json.dumps(trace))
    res = serve_continuous("qwen2.5-3b", num_slots=2, layers=1, d_model=32,
                           trace_file=str(tf))
    assert res["num_requests"] == 2
    ids = {r["id"] for r in res["requests"]}
    assert ids == {0, "explicit"}
    by_id = {r["id"]: r for r in res["requests"]}
    assert by_id["explicit"]["prompt_len"] == 4
    assert len(by_id["explicit"]["tokens"]) == 1


# ---------------------------------------------------------------------------
# deadlines and admission policy
# ---------------------------------------------------------------------------
def test_deadline_retires_without_slot_leak():
    """A deadline expiring mid-decode retires the request with
    stop_reason='deadline' and the tokens produced in time; one expiring
    in the queue yields an empty completion; neither leaks a slot (the
    scheduler asserts on drain) and the freed slot serves the rest."""
    cfg = _small_cfg()
    params = _PARAMS_CACHE.setdefault(
        "plain", init_lm(cfg, jax.random.PRNGKey(0)))
    # ids sort a < b < c: "a" admits first into the single slot
    reqs = [Request(request_id="a", tokens=np.arange(3, dtype=np.int32),
                    max_new_tokens=10, arrival=0.0, deadline=4.0),
            Request(request_id="b", tokens=np.arange(5, dtype=np.int32),
                    max_new_tokens=6, arrival=0.0),
            Request(request_id="c", tokens=np.arange(4, dtype=np.int32),
                    max_new_tokens=10, arrival=0.0, deadline=0.5)]
    sched = ContinuousScheduler(params, cfg, num_slots=1, prompt_pad=8,
                                max_len=18)
    res = sched.run(reqs)
    by = {c.request_id: c for c in res.completions}
    assert by["a"].stop_reason == "deadline"
    assert 0 < by["a"].tokens.shape[0] < 10
    # the produced prefix is still the exact static tokens
    ref = static_generate(params, cfg, reqs[0].tokens, 10)
    np.testing.assert_array_equal(by["a"].tokens,
                                  ref[:by["a"].tokens.shape[0]])
    assert by["c"].stop_reason == "deadline"
    assert by["c"].tokens.shape[0] == 0
    assert by["b"].stop_reason == "budget"
    assert by["b"].tokens.shape[0] == 6
    assert res.metrics["deadline_expiries"] == 2
    assert res.metrics["stop_reasons"]["deadline"] == 2


def test_deadline_validation():
    cfg = _small_cfg()
    params = _PARAMS_CACHE.setdefault(
        "plain", init_lm(cfg, jax.random.PRNGKey(0)))
    sched = ContinuousScheduler(params, cfg, num_slots=1, prompt_pad=8,
                                max_len=18)
    bad = [Request(request_id=0, tokens=np.arange(3, dtype=np.int32),
                   max_new_tokens=2, arrival=2.0, deadline=2.0)]
    with pytest.raises(ValueError, match="deadline"):
        sched.run(bad)
    with pytest.raises(ValueError, match="admission_policy"):
        ContinuousScheduler(params, cfg, num_slots=1, prompt_pad=8,
                            max_len=18, admission_policy="lifo")


def test_sjf_admission_improves_short_prompt_ttft():
    """Under 'sjf' a one-chunk prompt jumps a long chunked-prefill
    admission: its TTFT beats the FIFO run's, and tokens stay identical
    under both policies (admission order never changes content)."""
    cfg = _small_cfg()
    params = _PARAMS_CACHE.setdefault(
        "plain", init_lm(cfg, jax.random.PRNGKey(0)))

    def mk():
        return [Request(request_id="big",
                        tokens=np.arange(12, dtype=np.int32) % 100,
                        max_new_tokens=2, arrival=0.0),
                Request(request_id="small",
                        tokens=np.arange(2, dtype=np.int32) % 100,
                        max_new_tokens=2, arrival=0.0)]

    ttft, toks = {}, {}
    for pol in ("fifo", "sjf"):
        sched = ContinuousScheduler(params, cfg, num_slots=2,
                                    prompt_pad=12, max_len=16,
                                    prefill_chunk=2, admission_policy=pol)
        res = sched.run(mk())
        ttft[pol] = {c.request_id: c.ttft_steps for c in res.completions}
        toks[pol] = res.tokens_by_id()
        assert res.metrics["admission_policy"] == pol
    assert ttft["sjf"]["small"] < ttft["fifo"]["small"], \
        "sjf must admit the short prompt ahead of the long admission"
    for rid in ("big", "small"):
        np.testing.assert_array_equal(toks["fifo"][rid], toks["sjf"][rid])
