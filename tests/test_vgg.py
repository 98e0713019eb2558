"""VGG16 through the CNN executor: the flattening head and the benchmark
cell's network.

A dense layer that flattens a map states the spatial size it flattens
(``DenseSpec.in_h``/``in_w``); the executor max-pools the map down to it
and flattens in (h, w, c) order. One that states none takes the global
average, as the ResNet, MobileNet and Inception heads do. The network is
the one the benchmark cell ``vgg16-imagenette.batch64`` builds
(``bench/drivers/vgg.py``), held to its plain reference
(``bench/reference/vgg.py``, loaded as the harness loads it) at the
configuration's rehearsal sizes, and traced for shapes at full size."""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT / "bench") not in sys.path:
    sys.path.insert(0, str(ROOT / "bench"))

from harness import cells, runner, seeds  # noqa: E402

from repro.core import pim  # noqa: E402
from repro.core.workloads import (DenseSpec, inceptionv2,  # noqa: E402
                                  mobilenet, resnet18, vgg16)
from repro.models.cnn import (cnn_forward, init_cnn,  # noqa: E402
                              plan_cnn_weights)

CELL = "vgg16-imagenette.batch64"


def _head(spec, x, w):
    """``spec`` alone through the float executor, weight ``w``, bias 0."""
    params = {spec.name: {"w": w, "b": jnp.zeros((w.shape[1],))}}
    return np.asarray(cnn_forward(params, [spec], x))


def test_flattening_head_max_pools():
    """fc1 of a 4x4x3 map stating 2x2 sees each 2x2 window's max, in
    (h, w, c) order; the planted map's means differ from its maxes."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 3))
    spec = DenseSpec("fc1", 2 * 2 * 3, 12, in_h=2, in_w=2)
    got = _head(spec, x, jnp.eye(12))
    blocks = np.asarray(x).reshape(2, 2, 2, 2, 2, 3)
    want = blocks.max(axis=(2, 4)).reshape(2, -1)
    assert not np.allclose(want, blocks.mean(axis=(2, 4)).reshape(2, -1))
    np.testing.assert_array_equal(got, want)
    # the pool sits under the layer's own scope, then ``pool``
    eqns = jax.make_jaxpr(lambda a: cnn_forward(
        {"fc1": {"w": jnp.eye(12), "b": jnp.zeros((12,))}}, [spec],
        a))(x).eqns
    assert [str(e.source_info.name_stack) for e in eqns
            if e.primitive.name == "reduce_window_max"] == ["fc1/pool"]
    # a map already at the stated size is flattened as it is
    np.testing.assert_array_equal(
        _head(spec, x[:, :2, :2], jnp.eye(12)),
        np.asarray(x[:, :2, :2]).reshape(2, -1))


@pytest.mark.parametrize("spec,hw", [
    (DenseSpec("fc1", 100, 4, in_h=2, in_w=2), 4),   # features disagree
    (DenseSpec("fc1", 12, 4, in_h=2, in_w=2), 5),    # 5 is no multiple
    (DenseSpec("fc1", 12, 4, in_h=2, in_w=2), 1),    # map too small
    (DenseSpec("fc1", 48, 4), 4),                    # flattens, states none
], ids=["features", "not-a-multiple", "too-small", "unstated"])
def test_unexplained_map_raises(spec, hw):
    x = jnp.ones((1, hw, hw, 3))
    with pytest.raises(ValueError, match="'fc1'"):
        _head(spec, x, jnp.ones((spec.in_features, 4)))


@pytest.mark.parametrize("layers", [resnet18(100, 32), mobilenet(10, 32),
                                    inceptionv2(10, 32)],
                         ids=["resnet18", "mobilenet", "inceptionv2"])
def test_global_average_heads_still_average(layers):
    head = next(l for l in layers if isinstance(l, DenseSpec))
    assert head.in_h == head.in_w == 0
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 2, head.in_features))
    w = jax.random.normal(jax.random.PRNGKey(2), (head.in_features, 4))
    want = np.asarray(jnp.mean(x, axis=(1, 2)) @ w)
    np.testing.assert_allclose(_head(dataclasses.replace(
        head, out_features=4), x, w), want, rtol=1e-6)


def test_vgg16_fc1_states_the_map_it_flattens():
    assert [(l.in_h, l.in_features) for l in vgg16(10, 224)
            if isinstance(l, DenseSpec)] == [(7, 25088), (0, 4096),
                                              (0, 4096)]
    assert vgg16(10, 32)[13].in_h == 1


def _cell(rehearse):
    cell = cells.find(CELL)
    cfg, mix = runner.effective(cell, rehearse)
    return cell, cfg, mix, cells.driver(cell)


@pytest.mark.parametrize("bits", [4, 8], ids=["w4a4", "w8a8"])
def test_pim_forward_matches_reference_bit_for_bit(bits):
    cell, cfg, mix, drv = _cell(True)
    cfg["pim"] = dict(cfg["pim"], weight_bits=bits, act_bits=bits)
    ref = cells.reference(cell)
    specs, pcfg = drv.layer_specs(cfg), drv.pim_config(cfg)
    key = seeds.jax_key(2**35 + 11, seeds.WEIGHTS)
    params = init_cnn(specs, key)
    plans = plan_cnn_weights(params, specs, pcfg)
    hw = cfg["image_size"]
    x = jax.random.normal(jax.random.PRNGKey(bits), (4, hw, hw, 3))
    got = np.asarray(jax.jit(lambda p, pl, a: cnn_forward(
        p, specs, a, pim=pcfg, plans=pl))(params, plans, x))
    with jax.default_matmul_precision("highest"):
        want = ref._logits(ref._weights(key, cfg), x, ref._freeze(cfg))
    assert got.shape == (4, cfg["num_classes"])
    assert np.isfinite(got).all() and np.abs(got).max() > 0
    np.testing.assert_array_equal(got, want)


def _full_size_shapes():
    _, cfg, mix, drv = _cell(False)
    specs, pcfg = drv.layer_specs(cfg), drv.pim_config(cfg)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_cnn(specs, k), key)
    plans = jax.eval_shape(lambda p: plan_cnn_weights(p, specs, pcfg),
                           params)
    hw = cfg["image_size"]
    x = jax.ShapeDtypeStruct((mix["batch"], hw, hw, 3), jnp.float32)
    fwd = lambda p, pl, a: cnn_forward(p, specs, a, pim=pcfg, plans=pl)
    return cfg, mix, drv, specs, (fwd, params, plans, x)


def test_full_size_forward_shape():
    """At 224 px and full widths the cell's network is Table II's VGG16
    and gives (64, 10) logits."""
    cfg, mix, _, specs, (fwd, *args) = _full_size_shapes()
    assert specs == vgg16(cfg["num_classes"], cfg["image_size"])
    out = jax.eval_shape(fwd, *args)
    assert out.shape == (mix["batch"], cfg["num_classes"])


def test_full_size_kernel_calls_match_the_drivers_counts():
    """The program's call log at full size lists the driver's logical
    calls, each under its layer's scope; fc1's pool makes no call."""
    cfg, mix, drv, specs, (fwd, *args) = _full_size_shapes()
    calls = pim.kernel_calls(fwd, *args)
    assert [(c.m, c.k, c.n) for c in calls] == \
        [(m, k, n) for m, k, n, _ in drv.calls(cfg, mix["batch"], 1)]
    assert [c.scope.split("/")[0] for c in calls] == \
        [s.name for s in specs]
    assert (calls[13].m, calls[13].k) == (64, 25088)
