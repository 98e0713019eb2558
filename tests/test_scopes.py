"""Named scopes and the kernel call log on the CNN path, and the trace
reduction that reads them (``bench/harness/scopes.py``).

The forward is the benchmark cell's, at its configuration's rehearsal
sizes; the pad-share reader runs at the cell's full shapes, which it
only traces."""
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT / "bench") not in sys.path:
    sys.path.insert(0, str(ROOT / "bench"))

from harness import cells, counts, runner, scopes  # noqa: E402
from harness import trace as trace_mod  # noqa: E402

from repro.core import pim  # noqa: E402
from repro.kernels.pim_matmul.pim_matmul import (  # noqa: E402
    kernel_tiles, plan_padding)

CELL = "resnet18-cifar100.batch1024"
TRACE = ROOT / "bench" / "tests" / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def rehearsal():
    cell = cells.find(CELL)
    cfg, mix = runner.effective(cell, True)
    drv = cells.driver(cell)
    return cell, cfg, mix, drv, drv.setup(cfg, mix, 2 ** 31 + 7)


def _issued(m, k, n, cfg):
    """(Mp, Kp, Np, bm, bn, bk) the kernel issues for a logical (M, K,
    N), from two contracts. The plan's padding, fixed once when the
    weights are programmed: K to whole 512-deep blocks and then to a WDM
    chunk, N to whole 128 lanes (``plan_padding``). The call's tiles,
    chosen per call from (M, Kp, Np) (``kernel_tiles``): they divide Kp
    and Np, and M rounds up to whole M tiles, the last possibly
    partial."""
    up = lambda v, b: -(-v // b) * b
    kp, np_ = plan_padding(k, n)
    kp = up(kp, min(cfg.wdm_chunk, k))
    bm, bn, bk = kernel_tiles(m, kp, np_, cfg.act_planes, cfg.weight_planes)
    assert (kp % bk, np_ % bn) == (0, 0)
    return up(m, bm), kp, np_, bm, bn, bk


def test_compiled_forward_puts_each_conv_under_its_scopes(rehearsal):
    _, cfg, _, drv, s = rehearsal
    text = s.forward.lower(s.biases, s.plans, s.pool[0]).compile().as_text()
    names = set(scopes.op_names(text).values())
    for spec in drv.layer_specs(cfg):
        want = [pim.SCOPE_ACT_QUANT, pim.SCOPE_KERNEL]
        if spec.name != "fc":
            want.append(pim.SCOPE_IM2COL)
        for scope in want:
            assert any(f"/{spec.name}/{scope}/" in n for n in names), \
                (spec.name, scope)
        if spec.name.endswith("c2"):
            assert any(f"/{spec.name}/{pim.SCOPE_RESIDUAL}/" in n
                       for n in names), spec.name
    assert not any(re.search("pim_matmul|forward", sc) for sc in pim.SCOPES)


def test_kernel_call_log_matches_logical_and_issued_shapes(rehearsal):
    _, cfg, mix, drv, s = rehearsal
    # the jitted forward has run already: the log must not come back
    # empty from JAX's trace cache
    log = pim.kernel_calls(s.forward.__wrapped__, s.biases, s.plans,
                           s.pool[0])
    want = counts.cnn_calls(cfg, mix["batch"], 1)
    assert [(c.m, c.k, c.n) for c in log] == [(m, k, n)
                                              for m, k, n, _ in want]
    pcfg = drv.pim_config(cfg)
    for c in log:
        assert (c.mp, c.kp, c.np, c.bm, c.bn, c.bk) == \
            _issued(c.m, c.k, c.n, pcfg)
        assert (c.pa, c.pw) == (pcfg.act_planes, pcfg.weight_planes)
    assert [c.scope for c in log] == [
        f"{spec.name}/{pim.SCOPE_KERNEL}" for spec in drv.layer_specs(cfg)]
    assert pim._CALL_LOG.get() is None       # recording is off outside


def test_pad_share_reader_at_the_cells_shapes():
    cell = cells.find(CELL)
    cfg, mix = runner.effective(cell, False)
    run = runner.Run(cell=cell, config=cfg, traffic=mix, counters={},
                     kernel_calls=[], true_int_ops=0, window_s=1.0,
                     trace=None, peaks=None, chips=1)
    got = cells.metric_reader("pim_matmul_pad_share.cnn").read(run)
    pcfg = cells.driver(cell).pim_config(cfg)
    logical = issued = 0
    for m, k, n, _ in counts.cnn_calls(cfg, mix["batch"], 1):
        mp, kp, np_ = _issued(m, k, n, pcfg)[:3]
        logical, issued = logical + m * k * n, issued + mp * kp * np_
    assert abs(got - 100.0 * (1 - logical / issued)) < 1e-9
    assert abs(got - 25.6) < 0.1


def test_op_names_join_compiler_added_instructions():
    text = """\
HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %neg.1 = f32[8]{0} negate(%param_0), metadata={op_name="jit(f)/s0b0c1/im2col/neg"}
  ROOT %dynamic-update-slice.2 = f32[8]{0} dynamic-update-slice(%neg.1, %param_0)
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %dynamic-update-slice.3 = f32[8]{0} dynamic-update-slice(%param_0.1, %param_0.1)
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %copy-start = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%fusion.2)
  %copy-done = f32[8]{0} copy-done(%copy-start)
  ROOT %tanh.1 = f32[8]{0} tanh(%copy-done), metadata={op_name="jit(f)/s0b0c1/kernel/tanh"}
}
"""
    names = scopes.op_names(text)
    im2col = "jit(f)/s0b0c1/im2col/neg"
    assert names["fusion.1"] == im2col        # its computation's own op
    assert names["fusion.2"] == im2col        # a split chunk: its operand
    assert names["copy-start"] == names["copy-done"] == im2col
    assert names["tanh.1"] == "jit(f)/s0b0c1/kernel/tanh"
    known = pim.SCOPES + ("s0b0c1",)
    assert scopes.innermost(names["tanh.1"], known) == pim.SCOPE_KERNEL
    assert scopes.innermost("jit(f)/s0b0c1/relu", known) == "s0b0c1"
    assert scopes.innermost(None, known) == scopes.UNSCOPED
    assert scopes.innermost(names["x.1"], known) == scopes.UNSCOPED


@pytest.mark.skipif(not TRACE.exists(), reason="no recorded trace")
def test_scope_reduction_attributes_an_op_to_its_own_instruction():
    """The recorded step is the kernel, then an XLA tanh that reads its
    output. The kernel pattern, searched in the whole event text, counts
    the tanh as kernel time; the scope of the tanh's own instruction
    does not."""
    ops = scopes.op_times(str(TRACE))
    kernel = "pim_matmul_fused_pallas.1"
    names = {kernel: "jit(step)/kernel/pim_matmul_fused",
             "tanh.1": "jit(step)/epilogue/tanh",
             "fusion.6": "jit(step)/act_quant/convert_element_type"}
    by = scopes.scope_times(ops, names, pim.SCOPES + ("epilogue",))
    own = {scopes.instruction(n): t for n, t in ops.items()}
    assert by[pim.SCOPE_KERNEL] == pytest.approx(own[kernel])
    assert by["epilogue"] == pytest.approx(own["tanh.1"])
    assert by[pim.SCOPE_ACT_QUANT] == pytest.approx(own["fusion.6"])
    assert sum(by.values()) == pytest.approx(sum(ops.values()))
    red = trace_mod.reduce_trace(str(TRACE), {}, {"k": r"pim_matmul"})
    assert red.kernel_s["k"] == pytest.approx(
        by[pim.SCOPE_KERNEL] + by["epilogue"])
    assert by[pim.SCOPE_KERNEL] < red.kernel_s["k"] <= red.busy_s
