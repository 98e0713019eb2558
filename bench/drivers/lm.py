"""Drive a decoder LM through the program's serving path as a client.

Set-up builds the model from the seed on the device in one jitted call
(``init_lm`` then ``plan_params_for_pim``: every projection programmed
into stationary plans, the embedding fake-quantized, the float32 tree
never kept), builds one ``ContinuousScheduler`` over ``ServingEngine``
and warms its programs with the mix's own shapes.

The window is a closed loop of rounds, as an offline batch job runs: a
round's requests are submitted at once, and the next round is submitted
when the previous one has drained. The client stamps every token on the
host clock as ``on_token`` delivers it, and the window closes at the
first token delivered past its end (that round is cut there). The
engine's verbs are wrapped, from here, with host spans and counters.
"""
from __future__ import annotations

import functools
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from harness import counts, seeds, stats, traffic


class WindowClosed(Exception):
    pass


def model_config(cfg: Dict):
    """The configuration file as the program's ``ModelConfig``."""
    from repro.configs.base import ModelConfig
    hd = cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense", block_type="attn",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=hd,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["qkv_bias"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], activation=cfg["hidden_act"],
        attn_block=cfg["attention_block"],
        blockwise_threshold=cfg["blockwise_above"])


def pim_config(cfg: Dict):
    from repro.core.pim import PimConfig
    p = cfg["pim"]
    return PimConfig(weight_bits=p["weight_bits"], act_bits=p["act_bits"],
                     substrate=p["substrate"])


def build_params(mcfg, pcfg, key):
    """Weights from the seed, programmed, in one jitted call."""
    import jax
    from repro.launch.serve import plan_params_for_pim
    from repro.models.lm import init_lm

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def make(k, mcfg, pcfg):
        return plan_params_for_pim(init_lm(mcfg, k), pcfg)

    return make(key, mcfg, pcfg)


class _Client:
    """Stream callbacks of the client: wall-clock stamps per token."""

    def __init__(self, deadline: float):
        self.deadline = deadline
        self.t_round = 0.0
        self.last: Dict[str, float] = {}
        self.ttft: List[float] = []
        self.gaps: List[float] = []
        self.tokens = 0
        self.finished: Dict[str, object] = {}

    def on_admit(self, request_id, slot, step) -> None:
        pass

    def on_token(self, request_id, token, index) -> None:
        t = time.perf_counter()
        if t > self.deadline:
            raise WindowClosed
        if index == 0:
            self.ttft.append(t - self.t_round)
        else:
            self.gaps.append(t - self.last[request_id])
        self.last[request_id] = t
        self.tokens += 1

    def on_finish(self, completion) -> None:
        self.finished[completion.request_id] = completion


class Session:
    def __init__(self, cfg: Dict, mix: Dict, seed: int,
                 fault: Optional[str] = None):
        import jax
        import jax.numpy as jnp
        from repro.serving.scheduler import ContinuousScheduler
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.mcfg = model_config(cfg)
        params = build_params(self.mcfg, pim_config(cfg),
                              seeds.jax_key(seed, seeds.WEIGHTS))
        self.sched = ContinuousScheduler(
            params, self.mcfg, num_slots=mix["slots"],
            prompt_pad=mix["prompt_pad"], max_len=mix["max_len"],
            cache_dtype=getattr(jnp, cfg["kv_cache_dtype"]),
            sync_every=mix["sync_every"], prefill_chunk=mix["prefill_chunk"],
            prefix_cache=mix["prefix_cache"])
        del params
        self.counters: Dict[str, int] = {}
        self._wrap_engine(fault)
        # warm every program and host path the window takes: the engine's
        # step functions, then one short round through the scheduler
        self.sched.warmup()
        g = seeds.rng(seed, 9)
        warm = [self._request(f"w{i}", g.integers(
            0, self.mcfg.vocab_size, size=(mix["prompt"]["min"],)).astype(
                np.int32), 3) for i in range(2)]
        self.sched.run(warm)
        jax.block_until_ready(self.sched.engine.params)

    @staticmethod
    def _request(rid, tokens, max_new):
        from repro.serving.scheduler import Request
        return Request(request_id=rid, tokens=tokens, max_new_tokens=max_new)

    def _wrap_engine(self, fault: Optional[str]) -> None:
        """Host spans and counters around the engine's verbs; with
        ``fault`` set, a deliberately broken engine for the harness's own
        tests."""
        import jax
        eng, c = self.sched.engine, self.counters
        generate0, prefill0, insert0 = (eng.generate, eng.prefill_step,
                                        eng.insert)
        vocab = self.mcfg.vocab_size

        def generate(state, max_steps=None):
            n = len(state.slots)
            with jax.profiler.TraceAnnotation("decode"):
                state, res = generate0(state, max_steps)
            if fault == "token" and res.events:
                # a served token altered where the engine produces it
                ev = res.events[0]
                views = {v.slot: v for v, _ in res.finished}
                views.update(state.slots)
                ev.token = (ev.token + 1) % vocab
                views[ev.slot].tokens[ev.index] = ev.token
            c["decode_steps"] = c.get("decode_steps", 0) + res.steps
            c["slot_steps"] = c.get("slot_steps", 0) + res.steps * n
            c["decode_tokens"] = c.get("decode_tokens", 0) + len(res.events)
            return state, res

        def prefill_step(task):
            ran = not task.finished
            with jax.profiler.TraceAnnotation("prefill"):
                done = prefill0(task)
            if ran:
                c["prefills"] = c.get("prefills", 0) + 1
                c["prompt_tokens"] = c.get("prompt_tokens", 0) + task.length
            return done

        def insert(prefix, state, **kw):
            with jax.profiler.TraceAnnotation("insert"):
                return insert0(prefix, state, **kw)

        eng.generate, eng.prefill_step, eng.insert = (generate, prefill_step,
                                                      insert)
        if fault == "stale_state":
            # the prompt's KV never reaches the slot: insert returns the
            # cache it was given
            eng._insert_fn = lambda cache, k, v, slot, length: cache

    def window(self, seconds: float) -> Dict:
        import jax
        from repro.analysis.sanitize import CompileCounter
        self.counters.clear()
        client = _Client(0.0)
        vocab = self.mcfg.vocab_size
        attempted = rounds = 0
        with CompileCounter() as compiles, \
                jax.profiler.TraceAnnotation("window"):
            t0 = time.perf_counter()
            client.deadline = t0 + seconds
            try:
                while True:
                    reqs = [self._request(rid, toks, n) for rid, toks, n in
                            traffic.lm_round(self.mix, vocab, self.seed,
                                             rounds)]
                    attempted += len(reqs)
                    client.t_round = time.perf_counter()
                    with jax.profiler.TraceAnnotation("round"):
                        self.sched.run(reqs, client)
                    rounds += 1
            except WindowClosed:
                pass
            t_close = time.perf_counter()
        failed = sum(
            1 for comp in client.finished.values()
            if comp.stop_reason != "budget"
            or not ((comp.tokens >= 0) & (comp.tokens < vocab)).all())
        p95 = lambda xs: (None if len(xs) < 20
                          else 1e3 * stats.percentile(xs, 95))
        c = self.counters
        return {
            "end_to_end": {"out_tokens_per_s": client.tokens / seconds,
                           "ttft_p95_ms": p95(client.ttft),
                           "itl_p95_ms": p95(client.gaps)},
            "attempted": attempted, "failed": failed,
            "window_s": t_close - t0,
            "compiles": sum(compiles.counts.values()),
            "counters": dict(c, rounds=rounds, requests_done=len(
                client.finished), ttft_samples=len(client.ttft),
                gap_samples=len(client.gaps)),
            "samples": self._sample(client.finished),
        }

    def kernel_calls(self) -> List[counts.Call]:
        """Logical ``pim_matmul`` calls of the window, from its counts:
        every decode step drives all slots' rows, every prefill the
        padded prompt."""
        c = self.counters
        return (counts.lm_calls(self.cfg, self.mix["slots"],
                                c.get("decode_steps", 0))
                + counts.lm_calls(self.cfg, self.mix["prompt_pad"],
                                  c.get("prefills", 0)))

    def true_int_ops(self) -> int:
        """Integer operations of the projections at true lengths: real
        prompt tokens and real decoded tokens only."""
        c = self.counters
        return counts.lm_int_ops_per_token(self.cfg) * (
            c.get("prompt_tokens", 0) + c.get("decode_tokens", 0))

    def _sample(self, finished: Dict) -> List[Dict]:
        """Requests finished in the window that the reference re-reads:
        the longest, then others drawn from the seed, up to the mix's
        count of served tokens or of requests."""
        chk = self.mix["check"]
        done = sorted(finished.values(), key=lambda c: str(c.request_id))
        if not done:
            return []
        longest = max(done, key=lambda c: len(c.prompt) + len(c.tokens))
        rest = [c for c in done if c is not longest]
        order = seeds.rng(self.seed, 7).permutation(len(rest))
        pick, served = [longest], len(longest.tokens)
        for i in order:
            if served >= chk["tokens"] or len(pick) >= chk["max_requests"]:
                break
            pick.append(rest[i])
            served += len(rest[i].tokens)
        return [{"id": str(c.request_id), "prompt": np.asarray(c.prompt),
                 "tokens": np.asarray(c.tokens)} for c in pick]

    def free(self) -> None:
        self.sched = None
        gc.collect()


def setup(cfg: Dict, mix: Dict, seed: int, fault: Optional[str] = None
          ) -> Session:
    return Session(cfg, mix, seed, fault)


PROGRAMS = {"decode": r"^jit_decode\b", "prefill": r"^jit_prefill\b",
            "insert": r"^jit_insert\b"}
HOST_SPANS = ("decode", "prefill", "insert", "round")
KERNELS = {"pim_matmul": r"pim_matmul"}
