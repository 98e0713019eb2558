"""Serving driver: static batched prefill + decode, or continuous
batching through :mod:`repro.serving`, with optional OPIMA-PIM weight
execution (the paper's weight-stationary deployment path for LMs).

Two serving modes:

  * static (default): one batch, lock-step decode — every request shares
    a prompt length and finishes together.
  * ``--continuous``: synthetic Poisson (or trace-driven) arrivals with
    heterogeneous prompt/generation lengths stream through the
    continuous-batching scheduler — a fixed pool of decode slots over
    the same programmed plans, prefill interleaved with in-flight decode,
    retired slots refilled immediately (see repro/serving/).

``--metrics-json PATH`` dumps the full structured result (wall-clock
tokens/s, per-request latency percentiles in continuous mode, the OPIMA
hardware estimate) so benchmark trajectories parse a file, not stdout.

With ``--pim``, projection weights (attention q/k/v/o, MLP up/gate/down,
shared-expert MLPs) *and* MoE expert stacks are *programmed once* into
planned 'OPCM' form through :mod:`repro.engine` — quantized to 4-bit
cells, nibble-decomposed, pre-padded for the Pallas kernel — and the
serving matmuls drive activations past the stationary plans. The route is
selected by substrate name, one of :func:`repro.engine.available_substrates`:

  --pim-substrate exact-pallas   bit-exact integer datapath, fused dequant
                                 epilogue in the Pallas kernel (default)
  --pim-substrate exact-jnp      same math in plain jnp (bit-identical on
                                 this path — serving fuses no bias)
  --pim-substrate analog         photodetector/ADC readout model in whole-
                                 array jnp (deterministic: no stochastic
                                 read noise during serving)
  --pim-substrate analog-pallas  the same readout model through the fused
                                 Pallas analog-readout kernel — the
                                 physically-faithful mode at serving
                                 speed (bit-identical to analog here)
  --pim-substrate emulate        weight-quantization-only float matmul
                                 (the historical --pim-emulate behaviour,
                                 now a first-class substrate)

Weights the engine does not cover yet (SSM projections, embedding tables)
keep the fake-quantize emulation so every substrate still models their
cell-density quantization. ``--plan-dir DIR`` persists the programmed
parameter tree via :func:`repro.engine.save_plans`, so a serving restart
skips re-programming (:func:`repro.engine.load_plans` restores it, plans
and all). An OPIMA hardware latency/energy estimate for the request batch
is reported next to the wall-clock numbers (beyond-paper extension: the
paper only evaluates CNNs).

Run (reduced, CPU):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
      --layers 2 --d-model 64 --batch 2 --prompt-len 16 --gen 8 --pim
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import pathlib
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro.configs.base import ModelConfig, get_config
from repro.core.perfmodel import network_perf, total_power_w
from repro.core.pim import PimConfig
from repro.core.workloads import DenseSpec
from repro.models.lm import decode_step, init_lm, prefill
from repro.quant.quantize import fake_quantize

# Weight suffixes the PIM deployment touches (layers.py naming
# conventions) — the single source of truth for both the plan path and
# the fake-quantize path.
PIM_WEIGHT_SUFFIXES = ("_dh", "_hd", "_vd", "_dn", "_edf", "_efd")
# Of those, the ones programmed onto the real engine: 2-D projections
# stacked over layers, and expert-stacked MoE tensors.
_PLANNED_PROJ_SUFFIXES = ("_dh", "_hd")
_EXPERT_STACK_SUFFIXES = ("_edf", "_efd")
# Blocks whose weights are planned (nested dicts, e.g. moe/shared, are
# walked recursively).
_PLANNED_BLOCKS = ("attn", "xattn", "mlp", "moe")


def plan_params_for_pim(params, cfg: PimConfig):
    """Program the deployable weights into planned 'OPCM' form.

    Each scan-stacked (L, K, N) projection in the attention /
    cross-attention / MLP / shared-expert blocks becomes a vmapped
    :class:`~repro.core.pim.DensePlan`, and each (L, E, K, N) MoE expert
    stack becomes a vmapped :class:`~repro.core.pim.ExpertStackedPlan` —
    quantize + nibble-decompose + kernel pre-pad happen here, once, at
    weight-programming time, on the substrate ``cfg`` names. The planned
    pytrees flow through ``lax.scan`` like any other parameter;
    ``layers.proj`` and ``moe_apply`` dispatch them onto the engine.

    Weights the planned path does not cover (SSM projections, embedding
    tables — any ``PIM_WEIGHT_SUFFIXES`` leaf without an engine route)
    keep quantize-dequantize fake-quantization so every substrate still
    models their cell-density programming.

    Each leaf is programmed by one jitted program: eager op-by-op
    execution would compile every primitive separately and hold each
    full-size f32 temporary (quantize, STE, nibble split) in device
    memory at once."""
    sub = engine.get_substrate(cfg.resolved_substrate)
    fake_q = jax.jit(fake_quantize, static_argnames=("bits", "axis"))

    def _cfg_for(keys):
        # with ABFT verification on, each planned weight gets its tree
        # path as violation-report tag so the reliability layer can map a
        # checksum violation back to the plan subtree to re-program
        if cfg.verify == "off" or cfg.abft_tag is not None:
            return cfg
        return dataclasses.replace(cfg, abft_tag="/".join(keys))

    @functools.partial(jax.jit, static_argnums=1)
    def _program_stack(v, c):
        return jax.vmap(lambda w: sub.program(w, c))(v)

    @functools.partial(jax.jit, static_argnums=1)
    def _program_expert_stack(v, c):
        return jax.vmap(lambda w: sub.program_experts(w, c))(v)

    def plan_stack(v, keys):
        return _program_stack(v, _cfg_for(keys))

    def plan_expert_stack(v, keys):
        return _program_expert_stack(v, _cfg_for(keys))

    def _will_plan(keys, name, x) -> bool:
        if not any(k in _PLANNED_BLOCKS for k in keys):
            return False
        ndim = getattr(x, "ndim", 0)
        return ((name.endswith(_PLANNED_PROJ_SUFFIXES) and ndim == 3) or
                (name.endswith(_EXPERT_STACK_SUFFIXES) and ndim == 4))

    def _quantizable(name, x) -> bool:
        return (getattr(x, "ndim", 0) >= 2 and
                name.endswith(PIM_WEIGHT_SUFFIXES))

    def _program_block(blk, keys):
        # eligibility predicates (_will_plan / _quantizable) are shared
        # with the q() pass below, so the two passes cannot drift apart
        out = {}
        for k, v in blk.items():
            if isinstance(v, dict):
                out[k] = _program_block(v, keys + [k])
            elif _will_plan(keys + [k], k, v):
                out[k] = (plan_expert_stack(v, keys + [k]) if v.ndim == 4
                          else plan_stack(v, keys + [k]))
            elif _quantizable(k, v):
                out[k] = fake_q(v, bits=cfg.weight_bits, axis=(v.ndim - 2,))
            else:
                out[k] = v
        return out

    def q(path, x):
        keys = [str(getattr(p, "key", "")) for p in path]
        name = keys[-1] if keys else ""
        if _will_plan(keys, name, x):
            return x   # replaced by a plan below; don't quantize twice
        if _quantizable(name, x):
            return fake_q(x, bits=cfg.weight_bits, axis=(x.ndim - 2,))
        return x

    out = dict(jax.tree_util.tree_map_with_path(q, params))
    for layers_key in ("layers", "enc_layers"):
        if layers_key not in params:
            continue
        layers = dict(out[layers_key])
        for blk in _PLANNED_BLOCKS:
            if blk in layers:
                # program from the *original* float weights: the engine
                # does its own cell quantization at programming time
                layers[blk] = _program_block(params[layers_key][blk],
                                             [layers_key, blk])
        out[layers_key] = layers
    return out


def opima_lm_estimate(cfg: ModelConfig, batch: int, prompt: int, gen: int,
                      pim: PimConfig) -> Dict[str, float]:
    """Map the request batch's GEMMs onto the OPIMA perf model (weight-
    stationary FC mapping, §IV.D) for a hardware-side estimate."""
    specs = []
    heads_dim = cfg.num_heads * cfg.head_dim
    kv_dim = cfg.num_kv_heads * cfg.head_dim
    tokens = batch * (prompt + gen)
    for li in range(cfg.num_layers):
        if cfg.block_type in ("attn", "hybrid"):
            specs += [DenseSpec(f"l{li}.q", cfg.d_model, heads_dim),
                      DenseSpec(f"l{li}.k", cfg.d_model, kv_dim),
                      DenseSpec(f"l{li}.v", cfg.d_model, kv_dim),
                      DenseSpec(f"l{li}.o", heads_dim, cfg.d_model)]
        if cfg.is_moe:
            # hardware sizing assumes the routed drive: only the k selected
            # experts' stationary arrays are driven per token (undriven
            # arrays cost nothing in a weight-stationary bank). The
            # software _moe_pim route computes all E experts for numerical
            # simplicity; that digital-emulation cost is not an OPIMA cost.
            ff = cfg.moe_d_ff * cfg.experts_per_token
            specs += [DenseSpec(f"l{li}.moe_up", cfg.d_model, 2 * ff),
                      DenseSpec(f"l{li}.moe_dn", ff, cfg.d_model)]
        elif cfg.d_ff:
            mult = 2 if cfg.gated_mlp else 1
            specs += [DenseSpec(f"l{li}.up", cfg.d_model, mult * cfg.d_ff),
                      DenseSpec(f"l{li}.dn", cfg.d_ff, cfg.d_model)]
    if not specs:
        # pure-SSM architectures map no FC/attention GEMMs onto the PIM
        # arrays; report an explicit all-zero estimate (uniform key set)
        return {
            "opima_latency_ms_per_token_batch": 0.0,
            "opima_energy_mj_per_token_batch": 0.0,
            "opima_request_s": 0.0,
            "opima_tokens_per_s": 0.0,
            "opima_power_w": total_power_w(),
        }
    perf = network_perf(cfg.name, specs, weight_bits=pim.weight_bits,
                        act_bits=pim.act_bits)
    # One weight-stationary pass of the network per sequential token step;
    # the batch's rows stream through the programmed arrays within a step,
    # so the request takes (prompt + gen) * latency_s and yields
    # batch * (prompt + gen) tokens => throughput = batch / latency_s.
    steps = prompt + gen
    total_s = perf.latency_s * steps
    return {
        "opima_latency_ms_per_token_batch": perf.latency_s * 1e3,
        "opima_energy_mj_per_token_batch": perf.energy_j * 1e3,
        "opima_request_s": total_s,
        "opima_tokens_per_s": tokens / total_s,
        "opima_power_w": total_power_w(),
    }


def _params_digest(params) -> str:
    """Content hash of the source parameter tree: restored plans must have
    been programmed from these exact weights, not merely a tree with the
    same arch name and geometry."""
    import hashlib
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(jax.device_get(leaf).tobytes())
    return h.hexdigest()[:16]


def _pim_params(params, cfg: ModelConfig, pim_cfg: PimConfig,
                plan_dir: Optional[str], mesh=None,
                mesh_spec: Optional[str] = None):
    """Program (or restore) the PIM parameter tree.

    With ``plan_dir`` set, a previously saved plan checkpoint is restored
    — serving restarts skip re-programming — and a fresh programming run
    is persisted for the next boot. The checkpoint records the model
    identity/geometry alongside the PIM operating point; any mismatch
    (different arch, reduced dims, substrate, bit width, or mesh layout)
    re-programs instead of serving stale plans. With ``mesh``, plans are
    split over the device mesh (:func:`engine.shard_plan_tree`) and saved
    shard stamps are re-placed on restore."""
    if not plan_dir:
        planned = plan_params_for_pim(params, pim_cfg)
        if mesh is not None:
            planned = engine.shard_plan_tree(planned, mesh)
        return planned
    # the digest hashes every weight host-side, so only pay for it when a
    # plan checkpoint is actually in play
    want = {"substrate": pim_cfg.resolved_substrate,
            "weight_bits": pim_cfg.weight_bits,
            "act_bits": pim_cfg.act_bits,
            "abft": pim_cfg.verify,
            "arch": cfg.name,
            "num_layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "vocab_size": cfg.vocab_size,
            "mesh": mesh_spec,
            "params_digest": _params_digest(params)}
    try:
        planned, _, extras = engine.load_plans(plan_dir, mesh=mesh)
    except FileNotFoundError:
        pass
    except Exception as e:  # noqa: BLE001 — any restore failure
        # (bad zip, leaf-count assertion, version-skewed PimConfig
        # fields, ...) must degrade to re-programming, not crash the
        # restart the checkpoint exists to speed up
        print(f"[serve] could not restore plans from {plan_dir} "
              f"({type(e).__name__}: {e}); re-programming")
    else:
        got = {k: extras.get(k) for k in want}
        if got == want:
            print(f"[serve] restored programmed plans from {plan_dir} "
                  f"(substrate={got['substrate']})")
            return planned
        # plans execute on the cfg stamped into them, so a stale
        # checkpoint must not masquerade as the requested route
        print(f"[serve] plan checkpoint at {plan_dir} was programmed "
              f"for {got}, requested {want}; re-programming")
    planned = plan_params_for_pim(params, pim_cfg)
    if mesh is not None:
        planned = engine.shard_plan_tree(planned, mesh)
    try:
        engine.save_plans(plan_dir, planned, extras=want)
        print(f"[serve] saved programmed plans to {plan_dir}")
    except OSError as e:
        # the in-memory programming already succeeded; an unwritable
        # plan_dir should cost the next restart, not this request
        print(f"[serve] could not save plans to {plan_dir} "
              f"({type(e).__name__}: {e}); serving without a checkpoint")
    return planned


def _resolve_substrate(pim_substrate: Optional[str],
                       pim_emulate: bool) -> str:
    if pim_emulate:
        # stacklevel: _resolve_substrate -> _setup -> serve* -> user
        warnings.warn("pim_emulate is deprecated; use "
                      "pim_substrate='emulate'", DeprecationWarning,
                      stacklevel=4)
        # None means "no explicit request" — any explicit substrate,
        # including exact-pallas, conflicts with the deprecated flag
        if pim_substrate not in (None, "emulate"):
            raise ValueError(
                "--pim-emulate (deprecated) conflicts with an explicit "
                f"--pim-substrate {pim_substrate!r}; drop --pim-emulate "
                "and pass --pim-substrate emulate instead")
        return "emulate"
    return pim_substrate or "exact-pallas"


# Where the persistent compilation cache lives when the environment does
# not name one: a fixed directory inside the checkout (the path is part of
# the cache key, so a directory that moves never hits).
DEFAULT_COMPILE_CACHE = (pathlib.Path(__file__).resolve().parents[3]
                         / ".jax_cache")


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point, so
    restarts reuse compiled executables instead of re-lowering every step
    function. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and no other directory is configured here; otherwise the cache
    goes to :data:`DEFAULT_COMPILE_CACHE`. The size/compile-time floors
    are dropped to zero: serving compiles few, hot programs. Called by
    entry points only (serve's ``main`` and ``chip_smoke.py``), never by
    library code. Returns the cache directory."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the cache latches its directory at the first compile; anything that
    # compiled during imports would otherwise keep it pointed nowhere
    compilation_cache.reset_cache()
    return cache_dir


def _setup(arch: str, layers: Optional[int], d_model: Optional[int],
           pim: bool, pim_bits: int, pim_emulate: bool,
           pim_substrate: Optional[str], plan_dir: Optional[str],
           mesh_spec: Optional[str] = None,
           abft: str = "off"):
    """Shared serve bring-up: config reduction, param init, and (with
    ``pim``) weight programming — identical for both serving modes, so
    continuous mode streams past exactly the plans static mode uses.

    ``layers`` alone cuts depth only: every published width and the full
    vocabulary stay. ``d_model`` selects the smoke reduction instead
    (small widths, a 512-token vocabulary, ``layers`` or 2 layers).

    ``mesh_spec`` ("dp,tp") builds a ("data", "model") device mesh and
    splits the programmed plans over it (:mod:`repro.engine.mesh`):
    column/row tensor-parallel for stacked projections, expert-parallel
    for MoE stacks, with everything else replicated."""
    mesh = None
    if mesh_spec:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(mesh_spec)
    cfg = get_config(arch)
    if d_model:
        cfg = cfg.reduced(num_layers=layers or 2, d_model=d_model,
                          vocab=min(cfg.vocab_size, 512))
    elif layers:
        cfg = cfg.with_depth(layers)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    substrate = _resolve_substrate(pim_substrate, pim_emulate)
    pim_cfg = PimConfig(weight_bits=pim_bits, act_bits=pim_bits,
                        substrate=substrate, verify=abft)
    if pim:
        params = _pim_params(params, cfg, pim_cfg, plan_dir, mesh=mesh,
                             mesh_spec=mesh_spec or None)
    elif mesh is not None:
        params = engine.replicate(params, mesh)
    return cfg, params, substrate, pim_cfg, mesh


def write_metrics_json(path: str, result: Dict[str, Any]) -> None:
    """Dump a serve result as structured JSON (np arrays -> lists), so
    benchmark trajectories stop parsing stdout."""
    def conv(v):
        if isinstance(v, np.ndarray):
            return v.tolist()
        if isinstance(v, (np.integer,)):
            return int(v)
        if isinstance(v, (np.floating,)):
            return float(v)
        if isinstance(v, dict):
            return {k: conv(x) for k, x in v.items()}
        if isinstance(v, (list, tuple)):
            return [conv(x) for x in v]
        return v
    with open(path, "w") as f:
        json.dump(conv(result), f, indent=2, sort_keys=True)
        f.write("\n")


def serve(arch: str, batch: int = 2, prompt_len: int = 16, gen: int = 8,
          layers: Optional[int] = None, d_model: Optional[int] = None,
          pim: bool = False, pim_bits: int = 4, pim_emulate: bool = False,
          greedy: bool = True, pim_substrate: Optional[str] = None,
          plan_dir: Optional[str] = None, mesh: Optional[str] = None,
          metrics_json: Optional[str] = None,
          stop_tokens: Sequence[int] = (),
          eos_token: Optional[int] = None) -> Dict[str, Any]:
    """Run one batched serve request; ``pim_substrate`` names the engine
    route (default ``exact-pallas``; ``pim_emulate=True`` is the
    deprecated spelling of ``pim_substrate="emulate"``). ``mesh`` is a
    "dp,tp" device-mesh spec — the programmed plans are split over the
    mesh and the batch matmuls run tensor/expert-parallel.

    ``stop_tokens`` / ``eos_token`` give the static path the same stop
    semantics as the serving engine, applied *post hoc*: the lock-step
    loop still runs the full ``gen`` steps (all rows finish together —
    that is what makes the mode static), then each row is truncated at
    its first stop token and classified. Greedy rows are independent, so
    the truncated prefix is exactly what the continuous engine emits for
    the same request."""
    cfg, params, substrate, pim_cfg, _ = _setup(
        arch, layers, d_model, pim, pim_bits, pim_emulate, pim_substrate,
        plan_dir, mesh_spec=mesh)

    rng = np.random.default_rng(0)
    batch_in: Dict[str, Any] = {
        "tokens": jnp.asarray(rng.integers(
            0, cfg.vocab_size, size=(batch, prompt_len)), jnp.int32)}
    extra = 0
    if cfg.vision_tokens:
        batch_in["patches"] = jnp.asarray(rng.standard_normal(
            (batch, cfg.vision_tokens, cfg.vision_dim)), jnp.float32)
        extra = cfg.vision_tokens
    if cfg.encoder_layers:
        batch_in["frames"] = jnp.asarray(rng.standard_normal(
            (batch, prompt_len, cfg.d_model)), jnp.float32)

    max_len = prompt_len + extra + gen

    # named (not lambdas) so compile-log lines read jit(serve_prefill) /
    # jit(serve_decode) — the sanitize compile sentinel keys on them
    def serve_prefill(p, b):
        return prefill(p, cfg, b, max_len=max_len)

    def serve_decode(p, c, t, i):
        return decode_step(p, cfg, c, t, i)

    prefill_fn = jax.jit(serve_prefill)
    decode_fn = jax.jit(serve_decode)

    t0 = time.time()
    logits, cache = prefill_fn(params, batch_in)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    # Collect tokens on-device during the timed loop: a host transfer per
    # step would force a device sync and pollute decode_s_per_token.
    out_tokens = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.time()
    for g in range(gen):
        out_tokens.append(tok)
        logits, cache = decode_fn(params, cache, tok,
                                  jnp.int32(prompt_len + extra + g))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = time.time() - t0

    total_s = t_prefill + t_decode
    generated = np.concatenate(jax.device_get(out_tokens), axis=1)
    # post-hoc stop semantics: truncate each row at its first stop token
    # and classify why it ended (mirrors Completion.stop_reason in
    # continuous mode; the stop token itself is the last emitted token)
    stop_set = {int(t) for t in stop_tokens}
    if eos_token is not None:
        stop_set.add(int(eos_token))
    is_stop = np.isin(generated, sorted(stop_set))
    reasons: List[str] = []
    emitted: List[List[int]] = []
    for row, row_stop in zip(generated.tolist(), is_stop):
        reason, cut = "budget", len(row)
        hits = np.flatnonzero(row_stop)
        if hits.size:
            cut = int(hits[0]) + 1
            reason = ("eos" if eos_token is not None
                      and row[cut - 1] == int(eos_token) else "stop_token")
        reasons.append(reason)
        emitted.append(row[:cut])
    reason_counts = {"budget": 0, "eos": 0, "stop_token": 0}
    for r in reasons:
        reason_counts[r] += 1
    result = {
        "mode": "static",
        "arch": cfg.name,
        "generated": generated,
        "prefill_s": t_prefill,
        "decode_s_per_token": t_decode / gen,
        "generated_tokens": batch * gen,
        "tokens_per_s": batch * gen / total_s if total_s > 0 else 0.0,
        # stop accounting: per-row reason + truncated sequences; the
        # lock-step loop computes (and counts) all batch*gen tokens
        # either way, so throughput fields above stay loop-accurate
        "stop_reasons": reason_counts,
        "row_stop_reasons": reasons,
        "emitted": emitted,
        "emitted_tokens": sum(len(e) for e in emitted),
    }
    if pim:
        result["pim_substrate"] = substrate
        result.update(opima_lm_estimate(cfg, batch, prompt_len, gen,
                                        pim_cfg))
    if metrics_json:
        write_metrics_json(metrics_json, result)
    return result


def _load_trace(trace_file: str, vocab: int, seed: int) -> List[Any]:
    """Trace-driven arrivals: a JSON list of request records, each with
    ``arrival`` (float steps) and either explicit ``tokens`` or a
    ``prompt_len`` (tokens drawn deterministically from ``seed``), plus
    ``gen`` (max new tokens)."""
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    with open(trace_file) as f:
        records = json.load(f)
    reqs = []
    for i, rec in enumerate(records):
        if "gen" not in rec:
            raise ValueError(
                f"trace record {i} in {trace_file} is missing 'gen' "
                f"(max new tokens): {rec}")
        if "tokens" in rec:
            toks = np.asarray(rec["tokens"], np.int32)
        elif "prompt_len" in rec:
            toks = rng.integers(0, vocab,
                                size=(int(rec["prompt_len"]),)).astype(
                                    np.int32)
        else:
            raise ValueError(
                f"trace record {i} in {trace_file} needs either "
                f"'tokens' or 'prompt_len': {rec}")
        deadline = rec.get("deadline")
        reqs.append(Request(
            request_id=rec.get("id", i), tokens=toks,
            max_new_tokens=int(rec["gen"]),
            arrival=float(rec.get("arrival", 0.0)),
            shared_prefix_len=int(rec.get("shared_prefix_len", 0)),
            deadline=float(deadline) if deadline is not None else None))
    return reqs


def serve_continuous(arch: str, num_slots: int = 4, num_requests: int = 16,
                     prompt_len: int = 16, gen: int = 8,
                     layers: Optional[int] = None,
                     d_model: Optional[int] = None, pim: bool = False,
                     pim_bits: int = 4, pim_emulate: bool = False,
                     pim_substrate: Optional[str] = None,
                     plan_dir: Optional[str] = None,
                     arrival_rate: float = 0.5,
                     trace_file: Optional[str] = None, seed: int = 0,
                     sync_every: int = 1, mesh: Optional[str] = None,
                     metrics_json: Optional[str] = None,
                     sanitize: bool = False,
                     stop_tokens: Sequence[int] = (),
                     eos_token: Optional[int] = None,
                     prefill_chunk: Optional[int] = None,
                     prefix_cache: int = 0,
                     shared_prefix: int = 0,
                     abft: str = "off",
                     inject_faults: Optional[str] = None,
                     admission_policy: str = "fifo",
                     chaos_check: bool = False) -> Dict[str, Any]:
    """Continuous-batching serve: requests with heterogeneous arrival
    times and prompt/generation lengths stream through a fixed pool of
    ``num_slots`` decode slots backed by the same programmed plans the
    static path uses.

    Without ``trace_file``, a synthetic Poisson trace is generated:
    exponential inter-arrivals at ``arrival_rate`` requests/step, prompt
    lengths mixed in [prompt_len//4, prompt_len], generation lengths in
    [max(1, gen//4), gen]. ``prompt_len``/``gen`` therefore bound the
    slot geometry: prompts pad to ``prompt_len`` (plus the shared
    prefix, when one is configured), the KV cache rows are
    ``prompt_pad + gen`` long.

    Serving-engine semantics pass straight through: ``stop_tokens`` /
    ``eos_token`` retire a slot the step its sequence finishes
    (on-device detection), ``prefill_chunk`` interleaves long prompts
    with decode one chunk per scheduler iteration, ``prefix_cache``
    (entry capacity) turns on content-hashed KV reuse, and
    ``shared_prefix`` prepends a common random prefix of that length to
    every synthetic prompt — the shared-system-prompt traffic shape the
    prefix cache exists for.

    Reliability knobs (see :mod:`repro.reliability`): ``abft`` stamps an
    ABFT column-checksum verify policy ("off" | "sample" | "always") on
    every programmed plan (requires ``pim``); ``inject_faults`` loads a
    fault-spec JSON and corrupts the programmed plans before serving —
    the ABFT checks detect the corruption at execute time and the
    engine's degradation machine retries the affected dispatch on the
    golden exact fallback, so completions stay correct; ``chaos_check``
    additionally runs the same trace fault-free first and asserts the
    injected run produced identical tokens and at least one detection.
    """
    from repro.serving import ContinuousScheduler, poisson_trace
    if shared_prefix < 0:
        raise ValueError("shared_prefix must be >= 0")
    if inject_faults and not pim:
        raise ValueError("--inject-faults requires --pim (faults target "
                         "programmed plans)")
    if inject_faults and abft == "off":
        raise ValueError("--inject-faults requires --abft sample|always "
                         "(without checksum verification the corruption "
                         "would go undetected)")
    cfg, params, substrate, pim_cfg, dev_mesh = _setup(
        arch, layers, d_model, pim, pim_bits, pim_emulate, pim_substrate,
        plan_dir, mesh_spec=mesh, abft=abft)
    if trace_file:
        requests = _load_trace(trace_file, cfg.vocab_size, seed)
        if not requests:
            raise ValueError(f"trace file {trace_file} contains no "
                             "requests")
        prompt_pad = max(int(np.asarray(r.tokens).shape[0])
                         for r in requests)
        max_len = prompt_pad + max(r.max_new_tokens for r in requests)
    else:
        p_lo = max(1, prompt_len // 4)
        g_lo = max(1, gen // 4)
        requests = poisson_trace(
            n=num_requests, rate=arrival_rate,
            prompt_lens=list(range(p_lo, prompt_len + 1)),
            gen_lens=list(range(g_lo, gen + 1)),
            vocab=cfg.vocab_size, seed=seed,
            shared_prefix_len=shared_prefix)
        prompt_pad = prompt_len + shared_prefix
        max_len = prompt_pad + gen
    sanitizer = None
    if sanitize:
        from repro.analysis.sanitize import Sanitizer
        sanitizer = Sanitizer(transfer_guard=True)
    golden_tokens = None
    if chaos_check:
        # fault-free reference pass over the same trace and plans: the
        # injected run below must reproduce these tokens bit-for-bit
        # through detection + fallback retry
        from repro.reliability import FAULT_LOG
        golden_sched = ContinuousScheduler(
            params, cfg, num_slots=num_slots, prompt_pad=prompt_pad,
            max_len=max_len, sync_every=sync_every, mesh=dev_mesh,
            stop_tokens=stop_tokens, eos_token=eos_token,
            prefill_chunk=prefill_chunk,
            admission_policy=admission_policy)
        golden_sched.warmup()
        golden_tokens = golden_sched.run(requests).tokens_by_id()
        FAULT_LOG.clear()
    manager = None
    if inject_faults or abft != "off":
        # ABFT without a fault spec still arms the manager: checks are
        # counted, violations drain per dispatch, and the metrics report
        # gains its reliability section (all zeros on a clean run)
        from repro.reliability import (ReliabilityManager,
                                       ReliabilityPolicy, load_fault_spec)
        models = load_fault_spec(inject_faults) if inject_faults else []
        manager = ReliabilityManager(
            params, models, ReliabilityPolicy(verify=abft))
    sched = ContinuousScheduler(params, cfg, num_slots=num_slots,
                                prompt_pad=prompt_pad, max_len=max_len,
                                sync_every=sync_every, mesh=dev_mesh,
                                sanitizer=sanitizer,
                                stop_tokens=stop_tokens,
                                eos_token=eos_token,
                                prefill_chunk=prefill_chunk,
                                prefix_cache=prefix_cache,
                                admission_policy=admission_policy,
                                reliability=manager)
    if sanitizer is not None:
        # every steady-state decode dispatch runs under
        # transfer_guard("disallow"), and the compile sentinel proves
        # each step function compiled exactly once (in warmup). Chunked
        # mode compiles prefill_chunk instead of the single-shot prefill.
        prefill_name = ("prefill_chunk" if sched.prefill_chunk is not None
                        else "prefill")
        names = (prefill_name, "insert", "decode", "decode_window")
        with sanitizer.compile_counter(names) as counter:
            sched.warmup()
            run = sched.run(requests)
        expected = {prefill_name: 1, "insert": 1, "decode": 1}
        if sync_every > 1:
            expected["decode_window"] = 1
        counter.expect(**expected)
    else:
        sched.warmup()   # keep first-call compile out of the metered run
        run = sched.run(requests)

    result: Dict[str, Any] = dict(run.metrics)
    if manager is not None:
        result["fault_spec"] = inject_faults
        result["injection_report"] = manager.injection_report
    if golden_tokens is not None:
        got = run.tokens_by_id()
        mismatched = [rid for rid, toks in golden_tokens.items()
                      if not np.array_equal(got.get(rid), toks)]
        rel = run.metrics.get("reliability") or {}
        detectable = sum(
            1 for e in (manager.injection_report if manager else [])
            if e.get("store_delta", 0) > 0)
        chaos = {"token_mismatches": len(mismatched),
                 "detectable_faults": detectable,
                 "detections": rel.get("detections", 0)}
        result["chaos_check"] = chaos
        if mismatched:
            raise AssertionError(
                f"chaos check failed: {len(mismatched)} request(s) "
                f"diverged from the fault-free run ({mismatched[:5]})")
        if detectable and not chaos["detections"]:
            raise AssertionError(
                "chaos check failed: faults were injected "
                f"({detectable} detectable) but ABFT reported no "
                "detection")
    if sanitizer is not None:
        result["sanitize"] = {**sanitizer.report(),
                              "compiles": dict(counter.counts)}
    result["arch"] = cfg.name
    if mesh:
        result["mesh"] = mesh
    result["requests"] = [
        {"id": c.request_id, "prompt_len": int(c.prompt.shape[0]),
         "tokens": c.tokens, "arrival_step": c.arrival_step,
         "ttft_steps": c.ttft_steps, "latency_steps": c.latency_steps,
         "stop_reason": c.stop_reason,
         "first_token_wall_s": c.first_token_wall_s}
        for c in run.completions]
    if pim:
        result["pim_substrate"] = substrate
        # OPIMA hardware-side estimate for the aggregate workload: one
        # weight-stationary pass of the network per sequential token
        # position (true prompt lengths — the hardware would not drive
        # pad positions) plus one per decode step; the slot batch's rows
        # stream through the programmed arrays within a pass.
        est = opima_lm_estimate(cfg, batch=1, prompt=0, gen=1, pim=pim_cfg)
        pass_s = est["opima_latency_ms_per_token_batch"] / 1e3
        total_passes = run.metrics["decode_steps"] + sum(
            int(c.prompt.shape[0]) for c in run.completions)
        if pass_s > 0:
            result["opima_latency_ms_per_token_batch"] = pass_s * 1e3
            result["opima_request_s"] = pass_s * total_passes
            result["opima_tokens_per_s"] = (
                run.metrics["generated_tokens"] / (pass_s * total_passes))
            result["opima_power_w"] = est["opima_power_w"]
    if metrics_json:
        write_metrics_json(metrics_json, result)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--layers", type=int, default=None,
                    help="cut depth to this many layers; alone it keeps "
                         "every published width and the full vocabulary")
    ap.add_argument("--d-model", type=int, default=None,
                    help="smoke reduction: shrink every width to this "
                         "d_model (vocabulary cut to 512)")
    ap.add_argument("--pim", action="store_true")
    ap.add_argument("--pim-bits", type=int, default=4)
    ap.add_argument("--pim-substrate", default=None,
                    choices=engine.available_substrates(),
                    help="engine substrate the programmed plans execute on "
                         "(default: exact-pallas)")
    ap.add_argument("--pim-emulate", action="store_true",
                    help="deprecated alias for --pim-substrate emulate")
    ap.add_argument("--plan-dir", default=None,
                    help="persist/restore programmed plans here so "
                         "restarts skip re-programming")
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="device mesh 'dp,tp': split programmed plans "
                         "tensor/expert-parallel over the model axis and "
                         "decode slots over the data axis (CPU: force "
                         "devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: Poisson/trace arrivals "
                         "through the slot scheduler (repro/serving/)")
    ap.add_argument("--num-slots", type=int, default=4,
                    help="decode-slot pool size (continuous mode)")
    ap.add_argument("--requests", type=int, default=16,
                    help="synthetic request count (continuous mode)")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="Poisson arrivals per decode step; <= 0 means "
                         "one burst at t=0 (continuous mode)")
    ap.add_argument("--trace-file", default=None,
                    help="JSON arrival trace instead of synthetic "
                         "Poisson traffic (continuous mode)")
    ap.add_argument("--sync-every", type=int, default=1,
                    help="fused decode steps per host sync (continuous "
                         "mode): >1 batches k steps on-device between "
                         "token syncs when no admission/retirement can "
                         "intervene; tokens are identical to 1")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stop-tokens", default=None, metavar="T1,T2,...",
                    help="comma-separated stop-token ids: a sequence "
                         "ends the step one is emitted (continuous mode: "
                         "detected on-device, slot retired immediately; "
                         "static mode: rows truncated post hoc)")
    ap.add_argument("--eos-token", type=int, default=None,
                    help="EOS token id (reported as stop_reason='eos'; "
                         "otherwise same semantics as --stop-tokens)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill (continuous mode): split "
                         "prompts into chunks of this many tokens, one "
                         "chunk per scheduler iteration, so long prompts "
                         "interleave with in-flight decode; tokens are "
                         "bit-identical to single-shot prefill")
    ap.add_argument("--prefix-cache", type=int, default=0, metavar="CAP",
                    help="content-hashed prefix-cache capacity in "
                         "entries (continuous mode): 0 disables; full-"
                         "prompt hits skip prefill, shared-prefix hits "
                         "(with --prefill-chunk) run only the tail")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="LEN",
                    help="prepend a common random prefix of LEN tokens "
                         "to every synthetic prompt (continuous mode; "
                         "the shared-system-prompt traffic shape)")
    ap.add_argument("--abft", default="off",
                    choices=("off", "sample", "always"),
                    help="ABFT column-checksum verification on every "
                         "programmed plan (requires --pim): 'sample' "
                         "checks one deterministic row per matmul, "
                         "'always' checks every row; violations feed "
                         "the reliability layer (continuous mode)")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC.json",
                    help="fault-injection spec (see repro.reliability."
                         "load_fault_spec): corrupt the programmed "
                         "plans before serving — stuck nibble planes, "
                         "ADC drift, dropped WDM chunks, bit-flips. "
                         "Requires --pim and --abft; detected "
                         "violations retry on the golden exact "
                         "fallback (continuous mode)")
    ap.add_argument("--chaos-check", action="store_true",
                    help="with --inject-faults: run the trace fault-"
                         "free first and assert the injected run "
                         "produced identical tokens and >=1 detection")
    ap.add_argument("--admission-policy", default="fifo",
                    choices=("fifo", "sjf"),
                    help="admission order (continuous mode): 'sjf' lets "
                         "a short prompt jump a long chunked-prefill "
                         "admission instead of strict FIFO")
    ap.add_argument("--metrics-json", default=None,
                    help="write the structured run metrics to this path")
    ap.add_argument("--sanitize", action="store_true",
                    help="arm the runtime sanitizers (continuous mode): "
                         "transfer_guard('disallow') around every "
                         "steady-state decode dispatch and a "
                         "compile-count sentinel asserting each step "
                         "function compiled exactly once")
    args = ap.parse_args()
    setup_compile_cache()
    stop_tokens = tuple(
        int(t) for t in args.stop_tokens.split(",") if t.strip()
    ) if args.stop_tokens else ()
    if args.continuous:
        res = serve_continuous(
            args.arch, num_slots=args.num_slots,
            num_requests=args.requests, prompt_len=args.prompt_len,
            gen=args.gen, layers=args.layers, d_model=args.d_model,
            pim=args.pim, pim_bits=args.pim_bits,
            pim_emulate=args.pim_emulate,
            pim_substrate=args.pim_substrate, plan_dir=args.plan_dir,
            arrival_rate=args.arrival_rate, trace_file=args.trace_file,
            seed=args.seed, sync_every=args.sync_every, mesh=args.mesh,
            metrics_json=args.metrics_json, sanitize=args.sanitize,
            stop_tokens=stop_tokens, eos_token=args.eos_token,
            prefill_chunk=args.prefill_chunk,
            prefix_cache=args.prefix_cache,
            shared_prefix=args.shared_prefix,
            abft=args.abft, inject_faults=args.inject_faults,
            admission_policy=args.admission_policy,
            chaos_check=args.chaos_check)
        if res.get("reliability"):
            rel = res["reliability"]
            print(f"[serve] reliability: {rel['injected_faults']} faults "
                  f"injected, {rel['checks']} checks, "
                  f"{rel['detections']} detections, {rel['retries']} "
                  f"retries, {rel['repairs']} repairs, "
                  f"degraded={rel['degraded']}")
        if res.get("chaos_check"):
            print(f"[serve] chaos check passed: {res['chaos_check']}")
        if args.sanitize:
            print(f"[serve] sanitize: transfer guard armed, compiles "
                  f"{res['sanitize']['compiles']}")
        print(f"[serve] continuous: {res['num_requests']} requests through "
              f"{res['num_slots']} slots, {res['decode_steps']} decode "
              f"steps in {res['host_syncs']} host syncs "
              f"(sync_every={res['sync_every']}), {res['prefills']} "
              f"prefills (traces: {res['prefill_traces']}/"
              f"{res['decode_traces']})")
        print(f"[serve] {res['generated_tokens']} tokens, "
              f"{res['tokens_per_s']:.1f} tok/s wall, "
              f"occupancy {res['mean_slot_occupancy']:.2f}")
        print(f"[serve] ttft p50/p90/p99 = {res['ttft_steps_p50']:.1f}/"
              f"{res['ttft_steps_p90']:.1f}/{res['ttft_steps_p99']:.1f} "
              f"steps; latency p50/p90/p99 = {res['latency_steps_p50']:.1f}/"
              f"{res['latency_steps_p90']:.1f}/"
              f"{res['latency_steps_p99']:.1f} steps")
        print(f"[serve] stop reasons: {res['stop_reasons']}" + (
            f"; prefix cache: {res['prefix_cache']}"
            if res.get("prefix_cache") else ""))
    else:
        res = serve(args.arch, args.batch, args.prompt_len, args.gen,
                    args.layers, args.d_model, args.pim, args.pim_bits,
                    args.pim_emulate, pim_substrate=args.pim_substrate,
                    plan_dir=args.plan_dir, mesh=args.mesh,
                    metrics_json=args.metrics_json,
                    stop_tokens=stop_tokens, eos_token=args.eos_token)
        print(f"[serve] prefill {res['prefill_s']*1e3:.1f}ms, "
              f"decode {res['decode_s_per_token']*1e3:.1f}ms/tok")
        print(f"[serve] tokens:\n{res['generated']}")
        if stop_tokens or args.eos_token is not None:
            print(f"[serve] stop reasons: {res['stop_reasons']}")
    if "pim_substrate" in res:
        print(f"[serve] pim_substrate = {res['pim_substrate']}")
    for k, v in res.items():
        if k.startswith("opima_"):
            print(f"[serve] {k} = {v:.4g}")
    if args.metrics_json:
        print(f"[serve] metrics written to {args.metrics_json}")


if __name__ == "__main__":
    main()
