"""Dense convs on an integer substrate quantize as im2col expands.

The executor takes each patch row's scale from the unexpanded map and
writes the taps' int8 planes straight into the plan's K layout
(``models/cnn._im2col_planes``), then drives the plan with them
(``engine.matmul_planes``). These tests hold it to the f32 patch route
it replaces (``_im2col``, then ``engine.matmul``), on every integer
substrate: the same codes, scales and outputs bit for bit, no float
patch matrix in the program, the same kernel calls; they check that
``emulate`` keeps the f32 patches; and they hold the float entry the LM
calls to the program it compiled to before the split.

Shapes are the benchmark configuration's rehearsal sizes
(``bench/configs/resnet18-cifar100.json``), plus one conv at stage 0's
channel count, whose K (576) the plan pads to 1024."""
import pathlib
import re
import sys

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT / "bench") not in sys.path:
    sys.path.insert(0, str(ROOT / "bench"))

from harness import cells, counts, runner  # noqa: E402

from repro import engine  # noqa: E402
from repro.core import pim  # noqa: E402
from repro.core.workloads import ConvSpec  # noqa: E402
from repro.kernels.pim_matmul import ops as pim_ops  # noqa: E402
from repro.models import cnn  # noqa: E402
from repro.quant.nibbles import to_nibbles  # noqa: E402
from repro.quant.quantize import quantize  # noqa: E402

CELL = "resnet18-cifar100.batch1024"


def _rehearsal():
    cell = cells.find(CELL)
    cfg, mix = runner.effective(cell, True)
    drv = cells.driver(cell)
    return cfg, mix, drv, drv.layer_specs(cfg)


def _pim(bits):
    return pim.PimConfig(weight_bits=bits, act_bits=bits,
                         substrate=pim.EXACT_PALLAS)


_, _, _, _LAYERS = _rehearsal()
_SPECS = {s.name: s for s in _LAYERS if isinstance(s, ConvSpec)}
# stage 0 at the cell's channels: K 576, which the plan pads to 1024
_SPECS["stage0_64ch"] = ConvSpec("stage0_64ch", 4, 4, 64, 64, 3, 3)
CONVS = ["stem",          # 3 channels, 3x3 stride 1, K 27 padded to 32
         "stage0_64ch",   # 3x3 stride 1, K 576 padded to 1024
         "s0b0c1",        # 3x3 stride 1, K 72
         "s1b0c1",        # 3x3 stride 2
         "s1b0ds"]        # 1x1 stride 2 downsample


def _map(spec, key):
    """A seeded input map with an all-zero quarter, so that some patch
    rows are all zero and take the scale's eps floor."""
    x = jax.random.normal(key, (2, spec.in_h, spec.in_w, spec.in_c))
    return x.at[:, :spec.in_h // 2, :spec.in_w // 2].set(0.0)


@pytest.mark.parametrize("bits", [4, 8], ids=["w4a4", "w8a8"])
@pytest.mark.parametrize("name", CONVS)
def test_conv_codes_and_output_match_f32_patches(name, bits):
    spec = _SPECS[name]
    cfg = _pim(bits)
    kw_, kx, kb = jax.random.split(jax.random.PRNGKey(bits), 3)
    w = jax.random.normal(kw_, (spec.kh, spec.kw, spec.in_c, spec.out_c))
    b = jax.random.normal(kb, (spec.out_c,))
    x = _map(spec, kx)
    plan = engine.program(w.reshape(-1, spec.out_c), cfg)
    k, kp = plan.k, plan.planes.shape[1]

    ap, scale = cnn._im2col_planes(x, spec, plan)
    cols = cnn._im2col(x, spec).reshape(-1, k)
    a_q = quantize(cols, bits, axis=(1,))
    assert ap.dtype == jnp.int8 and ap.shape == (cfg.act_planes,
                                                 cols.shape[0], kp)
    np.testing.assert_array_equal(scale, a_q.scale)
    np.testing.assert_array_equal(ap[:, :, :k],
                                  to_nibbles(a_q.values, bits))
    assert not np.asarray(ap[:, :, k:]).any()
    floor = np.float32(1e-8) / np.float32(2 ** (bits - 1) - 1)
    assert (np.asarray(scale) == floor).any()               # eps rows
    if name in ("stem", "stage0_64ch"):
        assert kp > k

    ex = cnn._Executor({name: {"w": w, "b": b}}, pim=cfg,
                       plans={name: plan})
    got = ex.conv(spec, x, relu=False)
    want = engine.matmul(cnn._im2col(x, spec), plan, cfg=cfg, bias=b)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)


# small convs for the other substrates, which interpret mode runs slowly:
# K 27 (3 channels) that the plan pads to the WDM chunk, and stride 2
_SMALL = {"k27": ConvSpec("k27", 6, 6, 3, 16, 3, 3),
          "k72_s2": ConvSpec("k72_s2", 6, 6, 8, 24, 3, 3, stride=2)}
# (substrate, verify); analog noise stays off (no rng), so every route
# is deterministic
_INTEGER_ROUTES = [("exact-jnp", "off"), ("analog", "off"),
                   ("analog-pallas", "off"), ("exact-pallas", "always")]


def _small_conv(spec, cfg, seed):
    kw_, kx, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
    w = jax.random.normal(kw_, (spec.kh, spec.kw, spec.in_c, spec.out_c))
    b = jax.random.normal(kb, (spec.out_c,))
    plan = engine.program(w.reshape(-1, spec.out_c), cfg)
    ex = cnn._Executor({spec.name: {"w": w, "b": b}}, pim=cfg,
                       plans={spec.name: plan})
    return ex, plan, b, _map(spec, kx)


@pytest.mark.parametrize("spec", list(_SMALL))
@pytest.mark.parametrize("substrate,verify", _INTEGER_ROUTES,
                         ids=[f"{s}-verify_{v}" for s, v in _INTEGER_ROUTES])
def test_integer_substrates_take_the_planes_route(substrate, verify, spec,
                                                  monkeypatch):
    """On every integer substrate, ABFT verify included, a dense conv
    writes int8 planes from the map and equals the f32 patch route bit
    for bit."""
    spec = _SMALL[spec]
    cfg = pim.PimConfig(substrate=substrate, verify=verify)
    ex, plan, b, x = _small_conv(spec, cfg, 7)
    assert plan.planes.shape[1] > plan.k or spec.stride == 2
    calls = []
    real = cnn._im2col_planes
    monkeypatch.setattr(cnn, "_im2col_planes",
                        lambda *a: calls.append(1) or real(*a))
    got = ex.conv(spec, x, relu=False)
    assert calls == [1]
    want = engine.matmul(cnn._im2col(x, spec), plan, bias=b)
    assert got.shape == want.shape == (2, spec.out_h, spec.out_w,
                                       spec.out_c)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, want)


def test_emulate_keeps_the_f32_patches(monkeypatch):
    """``emulate`` has no integer datapath: its dense conv builds the f32
    patch matrix and multiplies it through ``engine.matmul``, never the
    planes."""
    spec = _SMALL["k27"]
    cfg = pim.PimConfig(substrate=pim.EMULATE)
    ex, plan, b, x = _small_conv(spec, cfg, 8)
    monkeypatch.setattr(cnn, "_im2col_planes", None)   # must not be called
    got = ex.conv(spec, x, relu=False)
    want = engine.matmul(cnn._im2col(x, spec), plan, bias=b)
    np.testing.assert_array_equal(got, want)
    jaxpr = jax.make_jaxpr(lambda a: ex.conv(spec, a, relu=False))(x).jaxpr
    k = spec.kh * spec.kw * spec.in_c
    assert any(getattr(v, "shape", ()) == (2, spec.out_h, spec.out_w, k)
               and v.dtype == jnp.float32 for v in _avals(jaxpr))


def _f32_patch_conv(self, spec, x, w, bias):
    """The f32 patch route, spelled out: every patch row in f32, then
    ``engine.matmul`` on the layer's plan."""
    return engine.matmul(cnn._im2col(x, spec), self._plan(spec.name, w),
                         cfg=self.pim, bias=bias)


def _forward(bits, f32_patches, monkeypatch):
    cfg, mix, _, layers = _rehearsal()
    pcfg = _pim(bits)
    params = cnn.init_cnn(layers, jax.random.PRNGKey(5))
    params = {n: {"w": p["w"], "b": p["b"] + 0.1}
              for n, p in params.items()}
    plans = cnn.plan_cnn_weights(params, layers, pcfg)
    hw = cfg["image_size"]
    x = _map(ConvSpec("x", hw, hw, 3, 3, 1, 1), jax.random.PRNGKey(6))
    with monkeypatch.context() as mp:
        if f32_patches:
            mp.setattr(cnn._Executor, "_dense_conv", _f32_patch_conv)
        return np.asarray(jax.jit(lambda p, pl, a: cnn.cnn_forward(
            p, layers, a, pim=pcfg, plans=pl))(params, plans, x))


@pytest.mark.parametrize("bits", [4, 8], ids=["w4a4", "w8a8"])
def test_forward_logits_match_f32_patch_route(bits, monkeypatch):
    got = _forward(bits, False, monkeypatch)
    want = _forward(bits, True, monkeypatch)
    assert np.isfinite(got).all() and np.abs(got).max() > 0
    np.testing.assert_array_equal(got, want)


def _avals(jaxpr):
    """Every intermediate's abstract value, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (
                    param,):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jex_core.Jaxpr):
                    yield from _avals(sub)


def _patch_shaped(forward_args, layers, b):
    """The float or int32 intermediates shaped like a 3x3 conv's patch
    matrix, (B, oh, ow, K') in any order of (B, oh, ow) with K' at least
    its K, or (M, K). A 1x1 conv's patches are its strided input, which
    either route holds."""
    fn = forward_args[0]
    # a fresh closure: JAX would reuse an earlier trace of ``fn``
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*forward_args[1:]).jaxpr
    found = []
    for spec in layers:
        if not isinstance(spec, ConvSpec) or spec.kh * spec.kw == 1:
            continue
        k = spec.kh * spec.kw * spec.in_c
        lead = (b, spec.out_h, spec.out_w)
        for aval in _avals(jaxpr):
            shape = getattr(aval, "shape", ())
            wide = (len(shape) >= 4 and sorted(shape[-4:-1]) == sorted(lead)
                    and shape[-1] >= k)
            if (wide or tuple(shape) == (b * lead[1] * lead[2], k)) and (
                    jnp.issubdtype(aval.dtype, jnp.floating)
                    or aval.dtype == jnp.int32):
                found.append((spec.name, tuple(shape), str(aval.dtype)))
    return found


def test_forward_holds_no_float_patch_matrix(monkeypatch):
    cfg, mix, _, layers = _rehearsal()
    pcfg = _pim(4)
    params = cnn.init_cnn(layers, jax.random.PRNGKey(0))
    plans = cnn.plan_cnn_weights(params, layers, pcfg)
    hw, b = cfg["image_size"], mix["batch"]
    x = jnp.zeros((b, hw, hw, 3))
    fwd = lambda p, pl, a: cnn.cnn_forward(p, layers, a, pim=pcfg,
                                           plans=pl)
    assert _patch_shaped((fwd, params, plans, x), layers, b) == []
    # the check sees the f32 patches where they are
    with monkeypatch.context() as mp:
        mp.setattr(cnn._Executor, "_dense_conv", _f32_patch_conv)
        assert _patch_shaped((fwd, params, plans, x), layers, b)


def test_kernel_calls_unchanged_by_the_route(monkeypatch):
    """The call log (which ``pim_matmul_pad_share.cnn`` reads) lists
    every layer with the same shapes and scope on either route."""
    cfg, mix, _, layers = _rehearsal()
    pcfg = _pim(4)
    params = cnn.init_cnn(layers, jax.random.PRNGKey(0))
    plans = cnn.plan_cnn_weights(params, layers, pcfg)
    hw = cfg["image_size"]
    x = jnp.zeros((mix["batch"], hw, hw, 3))
    fwd = lambda p, pl, a: cnn.cnn_forward(p, layers, a, pim=pcfg, plans=pl)
    got = pim.kernel_calls(fwd, params, plans, x)
    with monkeypatch.context() as mp:
        mp.setattr(cnn._Executor, "_dense_conv", _f32_patch_conv)
        want = pim.kernel_calls(fwd, params, plans, x)
    assert got == want
    assert [c.scope for c in got] == [f"{s.name}/{pim.SCOPE_KERNEL}"
                                      for s in layers]
    assert [(c.m, c.k, c.n) for c in got] == [
        (m, k, n) for m, k, n, _ in counts.cnn_calls(cfg, mix["batch"], 1)]


def _unsplit_route(x, plan, cfg, bias):
    """The exact-pallas float entry as one body: quantize, planes, K pad,
    the fused kernel, under the same scopes."""
    x2 = x.reshape(-1, plan.k)
    with jax.named_scope(pim.SCOPE_ACT_QUANT):
        a_q = quantize(x2, bits=cfg.act_bits, axis=(1,))
        a_planes = to_nibbles(a_q.values, cfg.act_bits)
        pad_k = plan.planes.shape[1] - plan.k
        if pad_k:
            a_planes = jnp.pad(a_planes, ((0, 0), (0, 0), (0, pad_k)))
    with jax.named_scope(pim.SCOPE_KERNEL):
        pb = None
        if bias is not None:
            pb = jnp.pad(bias.astype(jnp.float32).reshape(1, -1),
                         ((0, 0), (0, plan.planes.shape[2] - plan.n)))
        out = pim_ops.pim_matmul_fused(a_planes, plan.planes, a_q.scale,
                                       plan.padded_scale, bias=pb,
                                       interpret=cfg.interpret,
                                       want_rowsum=False)
        out = out[:, :plan.n]
    return out.reshape(x.shape[:-1] + (plan.n,))


def _program_text(fn, *args):
    """The compiled program's computations without their metadata (the
    source-location tables before them, ``metadata={...}`` in them)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    text = text[re.search(r"^(%|ENTRY )", text, re.M).start():]
    return re.sub(r", metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("bits", [4, 8], ids=["w4a4", "w8a8"])
def test_lm_matmul_program_unchanged(bits, bias):
    """``engine.matmul`` on a ``DensePlan``, as the LM's projections call
    it, compiles to the unsplit route's program, instruction for
    instruction."""
    cfg = _pim(bits)
    kw_, kx, kb = jax.random.split(jax.random.PRNGKey(3), 3)
    plan = engine.program(jax.random.normal(kw_, (200, 96)), cfg)
    x = jax.random.normal(kx, (2, 5, 200))
    b = jax.random.normal(kb, (96,)) if bias else None
    got = _program_text(lambda a, p, c: engine.matmul(a, p, bias=c),
                        x, plan, b)
    want = _program_text(lambda a, p, c: _unsplit_route(a, p, cfg, c),
                         x, plan, b)
    assert got == want
    np.testing.assert_array_equal(engine.matmul(x, plan, bias=b),
                                  _unsplit_route(x, plan, cfg, b))
