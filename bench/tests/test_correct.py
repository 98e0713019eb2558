"""The harness's comparison on the CPU at the cells' rehearsal sizes.

Each run skips the look for a chip and drives the rest of a run (set-up,
window, reference). A sound program must come out correct while the
bfloat16 control (the reference computed one precision lower, put in
the program's place) fails its limit; and with the timed path broken
underneath, ``correct`` must come out false."""
import time

import pytest

import cellfiles
from harness import runner

LM = ("qwen2.5-3b.decode-batch", "qwen2.5-3b.prefill-long")
CNN = ("resnet18-cifar100.batch1024",)
CONTROL = {"qwen2.5-3b.decode-batch": "token_gap",
           "qwen2.5-3b.prefill-long": "token_gap",
           "resnet18-cifar100.batch1024": "logit_err"}


def _run(workload, fault=None, control=False, seed=2**33 + 5):
    code, result = runner.run(
        workload, seed, 2.0, False, t_start=time.perf_counter(),
        allow_cpu=True, rehearse=True, control=control, fault=fault,
        cell=cellfiles.find(workload), log=lambda msg: None)
    assert code == 3 and result["rehearsal"]
    return result


@pytest.mark.parametrize("workload", LM + CNN)
def test_sound_program_is_correct_and_control_is_not(workload):
    res = _run(workload, control=True)
    name = CONTROL[workload]
    assert res["correct"], res["checks"]
    limit = res["checks"][name]["limit"]
    assert res["readings"][name] <= limit
    assert res["readings"]["control_" + name] > limit, res["readings"]


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in LM for f in ("token", "stale_state")] + [
    (w, f) for w in CNN for f in ("answer", "half_batch")])
def test_broken_timed_path_is_not_correct(workload, fault):
    res = _run(workload, fault=fault)
    assert not res["correct"], res["checks"]
