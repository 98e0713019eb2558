"""The harness's comparison for ``vgg16-imagenette.batch64`` on the CPU
at its rehearsal sizes, as ``test_correct.py`` holds the other cells.

A sound program must come out correct while the bfloat16 control (the
reference computed one precision lower) fails the ``logit_err`` limit;
with the timed path broken underneath, ``correct`` must come out false.
The reference re-derives the program's weights from the seed on its
own."""
import time

import numpy as np
import pytest

import cellfiles
from harness import cells, runner, seeds

CELL = "vgg16-imagenette.batch64"


def _run(fault=None, control=False, seed=2**33 + 5):
    code, result = runner.run(
        CELL, seed, 2.0, False, t_start=time.perf_counter(),
        allow_cpu=True, rehearse=True, control=control, fault=fault,
        cell=cellfiles.find(CELL), log=lambda msg: None)
    assert code == 3 and result["rehearsal"]
    return result


def test_sound_program_is_correct_and_control_is_not():
    res = _run(control=True)
    assert res["correct"], res["checks"]
    limit = res["checks"]["logit_err"]["limit"]
    assert res["readings"]["logit_err"] <= limit
    assert res["readings"]["control_logit_err"] > limit, res["readings"]


@pytest.mark.parametrize("fault", ["answer", "half_batch"])
def test_broken_timed_path_is_not_correct(fault):
    res = _run(fault=fault)
    assert not res["correct"], res["checks"]


def test_reference_draws_the_programs_weights():
    cell = cellfiles.find(CELL)
    cfg, _ = runner.effective(cell, True)
    ref = cells.reference(cell)
    from repro.core.pim import PimConfig
    from repro.models.cnn import init_cnn, plan_cnn_weights
    layers = cells.driver(cell).layer_specs(cfg)
    key = seeds.jax_key(-(2**40) - 3, seeds.WEIGHTS)
    plans = plan_cnn_weights(init_cnn(layers, key), layers,
                             PimConfig(substrate="exact-jnp"))
    w = ref._weights(key, cfg)
    assert list(w) == list(plans)
    for n, plan in plans.items():
        np.testing.assert_array_equal(w[n][0], plan.values)
        np.testing.assert_array_equal(w[n][1], plan.scale)
