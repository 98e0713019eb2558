"""Plan-once / execute-many and substrate-sweep micro-benchmarks for the
weight-stationary PIM engine.

``plan_execute_bench`` measures repeated decode-shaped matmuls (small M,
LM-projection K x N) in two regimes:

  * ``replan_per_call`` — the pre-refactor behaviour: quantize + nibble-
    decompose + pad the weights inside every call (weights "move" every
    step, the internal-data-movement overhead PIM exists to eliminate).
  * ``planned``         — program the weights once with ``engine.program``
    and drive activations past the stationary planes each step.

Both run the identical exact datapath, so the delta is pure weight-plane
conversion overhead.

``substrate_sweep_bench`` drives one serve-shaped matmul (prefill-chunk M,
LM-projection K x N) through every registered execution substrate and
additionally reports the analog-jnp vs analog-pallas speedup and
peak-temp-memory delta: the jnp ``analog`` route materializes the whole
(planes, chunks, M, N) chunk-sum tensor, the fused kernel keeps the
readout chain in per-tile scratch. It also *asserts* the analog-readout
chunk-sum transient stays under 2 MiB per plane pair (the sub-blocked
fold — see ``repro.kernels.analog_readout``).

``mesh_sweep_bench`` splits the same serve-shaped plan column- and
row-wise over a 1/2/4-device mesh (``engine.shard_plan``) and checks the
sharded outputs bit-identical to single-device. On CPU the devices are
XLA host-platform virtuals sharing one machine, so wall clock is NOT
expected to drop with tp; the per-device stationary-work columns/rows
show the division of labour that scales on real hardware.

CPU wall clock — relative numbers only.

  PYTHONPATH=src python benchmarks/pim_plan_bench.py
"""
from __future__ import annotations

import os
import time
from typing import List, Optional, Tuple

if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # the mesh sweep needs virtual devices; harmless for the other benches
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=4").strip()

import jax
import numpy as np

Row = Tuple[str, float, str]

# decode step of a reduced LM projection: batch rows x (d_model, d_ff)
DECODE_M, DECODE_K, DECODE_N = 8, 512, 1024
# serve-shaped (prefill-chunk) matmul for the substrate sweep: the shape
# class where the analog jnp route's HBM intermediate actually hurts
SWEEP_M, SWEEP_K, SWEEP_N = 64, 1024, 1024
SWEEP_SUBSTRATES = ("exact-pallas", "exact-jnp", "analog", "analog-pallas")
WARMUP, ITERS = 2, 20


def _time(fn, *args, iters: int = ITERS) -> float:
    for _ in range(WARMUP):
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args).block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def _peak_temp_bytes(fn, *args) -> Optional[float]:
    """XLA's compiled temp-allocation size — the buffer-footprint lens on
    'no intermediate touches HBM'. None when the backend exposes no
    memory analysis."""
    try:
        mem = jax.jit(fn).lower(*args).compile().memory_analysis()
        return float(mem.temp_size_in_bytes)
    except Exception:
        return None


def plan_execute_bench() -> List[Row]:
    from repro import engine
    rows: List[Row] = []
    x = jax.random.normal(jax.random.PRNGKey(0), (DECODE_M, DECODE_K))
    w = jax.random.normal(jax.random.PRNGKey(1), (DECODE_K, DECODE_N))
    for bits in (4, 8):
        cfg = engine.PimConfig(weight_bits=bits, act_bits=bits,
                               substrate="exact-pallas")
        plan = engine.program(w, cfg)
        f_planned = jax.jit(lambda a, p=plan: engine.matmul(a, p))
        f_replan = jax.jit(
            lambda a, ww, c=cfg: engine.matmul(a, engine.program(ww, c)))
        t_planned = _time(f_planned, x)
        t_replan = _time(f_replan, x, w)
        rows += [
            (f"pim_plan.w{bits}a{bits}.planned.us_per_call", t_planned,
             "weights stationary (prepare once)"),
            (f"pim_plan.w{bits}a{bits}.replan_per_call.us_per_call",
             t_replan, "pre-refactor: decompose every call"),
            (f"pim_plan.w{bits}a{bits}.speedup", t_replan / t_planned,
             ">1 expected: plane decomposition amortized"),
        ]
    return rows


def substrate_sweep_bench() -> List[Row]:
    from repro import engine
    rows: List[Row] = []
    x = jax.random.normal(jax.random.PRNGKey(0), (SWEEP_M, SWEEP_K))
    w = jax.random.normal(jax.random.PRNGKey(1), (SWEEP_K, SWEEP_N))
    times, mems = {}, {}
    for sub in SWEEP_SUBSTRATES:
        cfg = engine.PimConfig(weight_bits=4, act_bits=4, substrate=sub)
        plan = engine.program(w, cfg)
        f = jax.jit(lambda a, p=plan: engine.matmul(a, p))
        # the analog jnp route is slow enough that fewer iters suffice
        times[sub] = _time(f, x, iters=5 if "analog" in sub else ITERS)
        mems[sub] = _peak_temp_bytes(lambda a, p=plan: engine.matmul(a, p),
                                     x)
        rows.append((f"pim_substrate.{sub}.us_per_call", times[sub],
                     f"serve-shaped {SWEEP_M}x{SWEEP_K}x{SWEEP_N} w4a4"))
        if mems[sub] is not None:
            rows.append((f"pim_substrate.{sub}.peak_temp_mib",
                         mems[sub] / 2**20, "XLA temp allocation"))
    rows.append(("pim_substrate.analog_pallas_speedup",
                 times["analog"] / times["analog-pallas"],
                 ">1 expected: readout chain fused in VMEM tiles"))
    if mems["analog"] is not None and mems["analog-pallas"] is not None:
        rows.append((
            "pim_substrate.analog_pallas_temp_mem_ratio",
            mems["analog"] / max(mems["analog-pallas"], 1.0),
            ">1 expected: no (planes,chunks,M,N) intermediate in HBM"))
    if mems["analog-pallas"] is not None:
        # the readout kernel walks the chunk axis one WDM chunk at a time;
        # a whole-tile chunk fold would put an 8 MiB (KC, bm, bn) tile per
        # plane pair back into the compiled temp allocation
        temp_mib = mems["analog-pallas"] / 2**20
        assert mems["analog-pallas"] < 8 * 2**20, (
            f"analog-pallas compiled temp {temp_mib:.2f} MiB at "
            f"{SWEEP_M}x{SWEEP_K}x{SWEEP_N} — chunk-sum transient "
            "regression?")
    return rows


MESH_TPS = (1, 2, 4)


def mesh_sweep_bench() -> List[Row]:
    from repro import engine
    from jax.sharding import Mesh
    rows: List[Row] = []
    x = jax.random.normal(jax.random.PRNGKey(0), (SWEEP_M, SWEEP_K))
    w = jax.random.normal(jax.random.PRNGKey(1), (SWEEP_K, SWEEP_N))
    cfg = engine.PimConfig(weight_bits=4, act_bits=4,
                           substrate="exact-pallas")
    base = engine.program(w, cfg)
    f = jax.jit(lambda a, p: engine.matmul(a, p))
    ref = jax.device_get(f(x, base))
    ndev = len(jax.devices())
    for tp in MESH_TPS:
        if tp > ndev:
            rows.append((f"pim_mesh.tp{tp}.skipped", 1.0,
                         f"only {ndev} devices visible"))
            continue
        mesh = Mesh(np.asarray(jax.devices()[:tp]), ("model",))
        for kind in ("col", "row"):
            plan = engine.shard_plan(base, mesh, kind) if tp > 1 else base
            t = _time(lambda a, p=plan: f(a, p), x)
            eq = np.array_equal(ref, jax.device_get(f(x, plan)))
            assert eq, f"sharded {kind} tp={tp} not bit-identical"
            work = (SWEEP_N if kind == "col" else SWEEP_K) // tp
            unit = "cols" if kind == "col" else "rows"
            rows += [
                (f"pim_mesh.{kind}.tp{tp}.us_per_call", t,
                 f"{SWEEP_M}x{SWEEP_K}x{SWEEP_N} w4a4; virtual CPU "
                 "devices share one core — wall clock is flat by design"),
                (f"pim_mesh.{kind}.tp{tp}.stationary_{unit}_per_device",
                 float(work), f"per-device share of the {unit} axis"),
                (f"pim_mesh.{kind}.tp{tp}.bitident_vs_single", float(eq),
                 "must be 1: exact substrates shard losslessly"),
            ]
    return rows


def main() -> None:
    print("name,value,derived")
    for name, value, derived in plan_execute_bench():
        print(f"{name},{value:.6g},{derived}")
    for name, value, derived in substrate_sweep_bench():
        print(f"{name},{value:.6g},{derived}")
    for name, value, derived in mesh_sweep_bench():
        print(f"{name},{value:.6g},{derived}")


if __name__ == "__main__":
    main()
