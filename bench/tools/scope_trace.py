"""Attribute a CNN cell's device time to the program's named scopes.

  python3 bench/tools/scope_trace.py <cell> --seed N --seconds S [--out DIR]

Sets the cell up as a run does, then measures three windows of S seconds:
profiler off, on, and off again. The traced window is reduced as a
run reduces it (busy time, the ``KERNELS`` pattern), and again by
scope: each op event's own instruction is joined to its ``op_name``
through the compiled forward's text (``harness/scopes.py``), and its
time goes to the innermost of the program's scopes (``SCOPES`` of
``repro.core.pim``) or layers that names it. The program's kernel call
log gives the kernel's tile padding.

Prints one JSON line: images/s of the three windows, busy and window
seconds, seconds per scope and per layer, the unscoped seconds, the
seconds the kernel pattern matches on instructions other than the
kernel's own, the kernel share and roofline share read from the pattern,
from the ``kernel`` scope and from the kernel's own instructions, the
padding share, and the seconds the scope map took to build. With
``--out`` it also writes the op times, the compiled text and the call
log there.
"""
import argparse
import dataclasses
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    from repro.core import pim

    from harness import cells, counts, runner, scopes
    from harness import trace as trace_mod
    cell = cells.find(args.cell)
    cfg, mix = runner.effective(cell, False)
    drv = cells.driver(cell)
    session = drv.setup(cfg, mix, args.seed)
    before = session.window(args.seconds)
    tdir = tempfile.mkdtemp(prefix="scope-trace-")
    jax.profiler.start_trace(tdir)
    try:
        traced = session.window(args.seconds)
    finally:
        jax.profiler.stop_trace()
    after = session.window(args.seconds)
    path = trace_mod.find_xplane(tdir)
    red = trace_mod.reduce_trace(path, drv.PROGRAMS, drv.KERNELS,
                                 drv.HOST_SPANS)
    ops = scopes.op_times(path)
    shutil.rmtree(tdir, ignore_errors=True)

    x = session.pool[0]
    t0 = time.perf_counter()
    text = session.forward.lower(session.biases, session.plans,
                                 x).compile().as_text()
    names = scopes.op_names(text)
    map_s = time.perf_counter() - t0
    calls = pim.kernel_calls(session.forward.__wrapped__, session.biases,
                             session.plans, x)
    layers = [spec.name for spec in drv.layer_specs(cfg)]
    by_scope = scopes.scope_times(ops, names, pim.SCOPES + tuple(layers))
    by_layer = scopes.scope_times(ops, names, layers)

    kern_re = re.compile(drv.KERNELS["pim_matmul"])
    over = sum(t for n, t in ops.items()
               if kern_re.search(n) and KERNEL_CALL not in n)
    own = sum(t for n, t in ops.items() if KERNEL_CALL in n)
    run = runner.Run(
        cell=cell, config=cfg, traffic=mix, counters=traced["counters"],
        kernel_calls=session.kernel_calls(),
        true_int_ops=session.true_int_ops(), window_s=traced["window_s"],
        trace=red, peaks=cells.peaks(jax.devices()[0].device_kind),
        chips=cell.chips)
    least, _ = counts.least_time_s(run.kernel_calls, run.peaks,
                                   cfg["pim"]["weight_bits"],
                                   cfg["pim"]["act_bits"])
    kern_scope = by_scope.get(pim.SCOPE_KERNEL, 0.0)
    logical = sum(c.m * c.k * c.n for c in calls)
    issued = sum(c.mp * c.kp * c.np for c in calls)
    out = {
        "cell": cell.name, "seed": args.seed,
        "device": jax.devices()[0].device_kind,
        "images_per_s": [w["end_to_end"]["images_per_s"]
                         for w in (before, traced, after)],
        "window_s": red.window_s, "busy_s": red.busy_s,
        "op_s": sum(ops.values()),
        "scope_s": by_scope, "layer_s": by_layer,
        "unscoped_s": by_layer.get(scopes.UNSCOPED, 0.0),
        "kernel_pattern_s": red.kernel_s.get("pim_matmul", 0.0),
        "pattern_overcount_s": over,
        "kernel_share_pattern": 100.0 * red.kernel_s.get(
            "pim_matmul", 0.0) / red.busy_s,
        "kernel_share_scope": 100.0 * kern_scope / red.busy_s,
        "roofline_pattern": counts.roofline_share(run, "pim_matmul"),
        "roofline_scope": 100.0 * least / kern_scope if kern_scope else None,
        "kernel_own_s": own,
        "kernel_share_own": 100.0 * own / red.busy_s,
        "roofline_own": 100.0 * least / own if own else None,
        "im2col_share": 100.0 * by_scope.get(pim.SCOPE_IM2COL, 0.0)
        / red.busy_s,
        "act_quant_share": 100.0 * by_scope.get(pim.SCOPE_ACT_QUANT, 0.0)
        / red.busy_s,
        "pad_share": 100.0 * (1 - logical / issued) if issued else None,
        "scope_map_s": map_s,
    }
    if args.out:
        d = pathlib.Path(args.out)
        d.mkdir(parents=True, exist_ok=True)
        (d / "op_times.json").write_text(json.dumps(ops))
        (d / "forward.hlo.txt").write_text(text)
        (d / "kernel_calls.json").write_text(json.dumps(
            [dataclasses.asdict(c) for c in calls]))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
