"""Self-check of the trace reduction: interval arithmetic by hand, and
the reduction of a small trace recorded on a TPU v5e
(``bench/tools/record_trace.py``) against a brute-force reading of the
same events."""
import pathlib

import pytest

from harness import cells, counts, trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_interval_arithmetic_by_hand():
    ivs = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (5.0, 5.0)]
    u = trace.union(ivs)
    assert u == [(0.0, 2.0), (3.0, 4.0), (5.0, 5.0)]
    assert trace.total(u) == 3.0
    assert trace.gaps(u, -1.0, 6.0) == [(-1.0, 0.0), (2.0, 3.0),
                                        (4.0, 5.0), (5.0, 6.0)]
    assert trace.clip((0.5, 2.0), 1.0, 1.5) == (1.0, 1.5)
    assert trace.clip((0.5, 2.0), 3.0, 4.0) is None
    assert trace.covers(u, 3.5) and trace.covers(u, 2.0)
    assert not trace.covers(u, 2.5) and not trace.covers(u, -0.1)


def test_roofline_arithmetic_by_hand():
    class Run:
        pass
    run = Run()
    run.config = {"pim": {"weight_bits": 4, "act_bits": 4}}
    run.peaks = cells.peaks("TPU v5 lite")
    # one (8192, 4096) x (4096, 4096) call: 2.7e11 ops, 5.5e8 bytes ->
    # compute-bound, 0.6994 ms at 393 TOP/s
    run.kernel_calls = [(8192, 4096, 4096, 1)]
    least, bound = counts.least_time_s(run.kernel_calls, run.peaks, 4, 4)
    assert bound == "compute"
    assert abs(least - 2 * 8192 * 4096 * 4096 / 393e12) < 1e-12
    run.trace = trace.Reduced(
        window_s=1.0, busy_s=0.5, program_s={}, kernel_s={
            "pim_matmul": 2 * least}, top_ops=[], idle_by_host=[],
        devices=1)
    assert abs(counts.roofline_share(run, "pim_matmul") - 50.0) < 1e-9
    run.trace.kernel_s = {}
    assert counts.roofline_share(run, "pim_matmul") is None


def test_misaligned_clocks_are_refused():
    trace.check_aligned(0.9, 1.0)
    with pytest.raises(ValueError, match="clocks disagree"):
        trace.check_aligned(0.4, 1.0)


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")
def test_recorded_trace_reduction():
    from jax.profiler import ProfileData
    red = trace.reduce_trace(str(DATA), {"step": r"^jit_step\b"},
                             {"pim_matmul": r"pim_matmul"}, ("host", "step"))
    # brute force over the same events: every device op inside the
    # host's window span, busy as a sorted sweep, kernel and program
    # sums by name
    pd = ProfileData.from_file(str(DATA))
    host = [(e.start_ns, e.start_ns + e.duration_ns)
            for p in pd.planes if p.name.startswith("/host:")
            for l in p.lines for e in l.events if e.name == "window"]
    lo, hi = host[0]
    dev = [p for p in pd.planes if p.name.startswith("/device:TPU")]
    assert len(dev) == red.devices == 1
    lines = {l.name: l for l in dev[0].lines}
    ops = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi), e)
           for e in lines[trace.OPS_LINE].events
           if e.start_ns + e.duration_ns > lo and e.start_ns < hi]
    busy, end = 0.0, lo
    for s, e, _ in sorted(ops, key=lambda x: x[0]):
        if e > end:
            busy += e - max(s, end)
            end = e
    assert abs(red.window_s - (hi - lo) * 1e-9) < 1e-9
    assert abs(red.busy_s - busy * 1e-9) < 1e-9
    kern = sum(e - s for s, e, ev in ops if "pim_matmul" in ev.name or any(
        isinstance(v, str) and "pim_matmul" in v for _, v in ev.stats))
    assert kern > 0 and abs(red.kernel_s["pim_matmul"] - kern * 1e-9) < 1e-9
    prog = sum(min(e.start_ns + e.duration_ns, hi) - max(e.start_ns, lo)
               for e in lines[trace.MODULES_LINE].events
               if e.name.startswith("jit_step")
               and e.start_ns + e.duration_ns > lo and e.start_ns < hi)
    assert prog > 0 and abs(red.program_s["step"] - prog * 1e-9) < 1e-9
    assert 0 < red.kernel_s["pim_matmul"] <= red.busy_s <= red.window_s
    # six steps ran, one after a 10 ms host sleep: that idle gap is the
    # host's
    idle = dict(red.idle_by_host)
    assert idle.get("host", 0.0) >= 0.009
