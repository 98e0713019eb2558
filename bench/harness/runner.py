"""One run of one cell: set-up, the measured window, the trace's
reduction, the comparison with the plain reference, the result line."""
from __future__ import annotations

import copy
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from harness import cells, trace as trace_mod


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader sees."""
    cell: cells.Cell
    config: Dict
    traffic: Dict
    counters: Dict
    kernel_calls: List
    true_int_ops: int
    window_s: float
    trace: Optional[trace_mod.Reduced]
    peaks: Optional[Dict]
    chips: int


def effective(cell: cells.Cell, rehearse: bool) -> Tuple[Dict, Dict]:
    """The configuration and mix as run: the files' contents, or with
    ``rehearse`` their tiny CPU-sized variants (the files' ``rehearsal``
    keys)."""
    cfg, mix = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    if rehearse:
        cfg.update(cfg.pop("rehearsal", {}))
        mix.update(mix.pop("rehearsal", {}))
    return cfg, mix


def compare(readings: Dict[str, float], limits: Dict, failed: int,
            compiles: int) -> Tuple[bool, Dict]:
    """Each number compared, beside its limit. A reading that is missing
    (the reference found nothing to read) fails."""
    checks = {"failed_requests": {"value": failed, "limit": 0},
              "compiles_in_window": {"value": compiles, "limit": 0}}
    for name, lim in limits.items():
        checks[name] = {"value": readings.get(name), "limit": lim["limit"]}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def device_peak_bytes(device) -> int:
    """The most device memory the process has held: its buffers' peak
    plus the peak of what the runtime reserves for compiled programs'
    temporaries, which a TPU keeps out of ``peak_bytes_in_use``."""
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) + \
        stats.get("peak_bytes_reserved", 0)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, allow_cpu: bool = False, rehearse: bool = False,
        control: bool = False, fault: Optional[str] = None,
        cell: Optional[cells.Cell] = None,
        log=lambda msg: print(msg, file=sys.stderr, flush=True)
        ) -> Tuple[int, Optional[Dict]]:
    """Run ``workload`` once (``cell``, where given, in place of its
    entry in ``BENCHMARK.json``). Returns (exit code, result); the result
    is None when nothing may be reported."""
    import jax
    cell = cell or cells.find(workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        log(f"no TPU: JAX found {devs[0].platform} devices; nothing ran")
        return 1, None
    if len(devs) < cell.chips:
        log(f"{workload} needs {cell.chips} chips, JAX found {len(devs)}")
        return 1, None
    if not rehearse:
        from repro.launch.serve import setup_compile_cache
        log(f"compile cache {setup_compile_cache()}")
    cfg, mix = effective(cell, rehearse)
    drv, ref = cells.driver(cell), cells.reference(cell)

    t_build = time.perf_counter()
    session = drv.setup(cfg, mix, seed, fault=fault)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.1f} s: {t_build - t_start:.1f} s to reach the "
        f"device, {setup_s - (t_build - t_start):.1f} s to build and warm")
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    try:
        win = session.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    log(f"window {win['window_s']:.2f} s, counters {win['counters']}")
    dev = devs[0]
    peak = max(device_peak_bytes(d) for d in devs[:cell.chips])
    reduced = None
    if trace:
        reduced = trace_mod.reduce_trace(
            trace_mod.find_xplane(tdir), drv.PROGRAMS, drv.KERNELS,
            drv.HOST_SPANS)
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace: window {reduced.window_s:.3f} s, busy "
            f"{reduced.busy_s:.3f} s, programs "
            f"{reduced.program_s}, kernels {reduced.kernel_s}, top ops "
            f"{reduced.top_ops[:5]}, idle {reduced.idle_by_host}")
    kernel_calls, int_ops = session.kernel_calls(), session.true_int_ops()
    session.free()
    del session
    gc.collect()

    t_ref = time.perf_counter()
    readings = ref.check(cfg, mix, seed, win["samples"], control=control)
    log(f"reference {time.perf_counter() - t_ref:.1f} s: {readings}")
    limits = cell.limits.get("rehearsal", {}) if rehearse \
        else cell.limits["checks"]
    ok, checks = compare(readings, limits, win["failed"], win["compiles"])
    for name, c in checks.items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")

    if rehearse:
        # a CPU run reports no device metric: counts and checks only
        return 3, {"rehearsal": True, "correct": ok,
                   "attempted": win["attempted"], "failed": win["failed"],
                   "counters": win["counters"], "readings": readings,
                   "checks": checks}

    result: Dict = {"correct": ok, "attempted": win["attempted"],
                    "failed": win["failed"]}
    if not trace:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in cell.end_to_end:
            v = win["end_to_end"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        r = Run(cell=cell, config=cfg, traffic=mix,
                counters=win["counters"], kernel_calls=kernel_calls,
                true_int_ops=int_ops, window_s=win["window_s"],
                trace=reduced, peaks=cells.peaks(dev.device_kind),
                chips=cell.chips)
        metrics = {}
        for m in cell.per_layer:
            v = cells.metric_reader(m["name"]).read(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        result["device"].update(busy_s=reduced.busy_s,
                                window_s=reduced.window_s)
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced.top_ops],
            "idle_gaps": [[n, s] for n, s in reduced.idle_by_host]}
    if control:
        result["control"] = {k: v for k, v in readings.items()
                             if k.startswith("control_")}
    result["readings"] = readings
    result["checks"] = checks
    return 0, result


def emit(result: Dict) -> None:
    print(json.dumps(result), flush=True)
