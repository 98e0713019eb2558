"""Weight-stationary engine: DensePlan reuse, decomposition-once
accounting, fused Pallas epilogue exactness, depthwise engine route, and
the serving metrics it feeds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.pim as pim_mod
from repro import engine
from repro.core.pim import (DensePlan, PimConfig, prepare_depthwise_weights,
                            prepare_weights, reference_quantized_matmul)
from repro.kernels.pim_matmul.pim_matmul import pim_matmul_fused_pallas
from repro.kernels.pim_matmul.ref import pim_matmul_fused_ref
from repro.quant.quantize import quantize


@pytest.mark.parametrize("wb,ab", [(4, 4), (8, 8)])
def test_planned_weights_reused_bit_identical(wb, ab):
    """A plan built once and executed twice (default Pallas route) is
    bit-identical to the un-sliced oracle both times."""
    cfg = PimConfig(weight_bits=wb, act_bits=ab)
    w = jax.random.normal(jax.random.PRNGKey(0), (96, 40))
    plan = prepare_weights(w, cfg)
    assert isinstance(plan, DensePlan)
    assert cfg.substrate == "exact-pallas", \
        "exact mode must default to the Pallas kernel"
    for seed in (1, 2):
        x = jax.random.normal(jax.random.PRNGKey(seed), (16, 96))
        assert jnp.array_equal(engine.matmul(x, plan, cfg=cfg),
                               reference_quantized_matmul(x, plan, cfg))


def test_plane_decomposition_once_per_weight_matrix(monkeypatch):
    """Nibble decomposition of the weight codes happens exactly once, at
    prepare_weights time — engine.matmul only ever decomposes
    activations."""
    calls = []
    real = pim_mod.to_nibbles

    def counting(codes, bits):
        calls.append(tuple(codes.shape))
        return real(codes, bits)

    monkeypatch.setattr(pim_mod, "to_nibbles", counting)
    cfg = PimConfig(weight_bits=4, act_bits=4)
    w = jax.random.normal(jax.random.PRNGKey(0), (96, 40))
    plan = prepare_weights(w, cfg)
    assert calls == [(96, 40)], "prepare must decompose the weights once"

    calls.clear()
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 96))
    for _ in range(3):
        engine.matmul(x, plan, cfg=cfg)
    assert calls == [(16, 96)] * 3, (
        f"engine.matmul must only decompose activations, saw {calls}")


def test_fused_epilogue_matches_jnp_path_exactly():
    """Default (fused Pallas) and jnp fallback agree to f32 bit-exactness
    on both 4-bit (one-plane) and 8-bit (two-plane) operands."""
    for bits in (4, 8):
        cfg_p = PimConfig(weight_bits=bits, act_bits=bits)
        cfg_j = PimConfig(weight_bits=bits, act_bits=bits,
                          substrate="exact-jnp")
        w = jax.random.normal(jax.random.PRNGKey(0), (200, 72))
        x = jax.random.normal(jax.random.PRNGKey(1), (33, 200))
        plan = prepare_weights(w, cfg_p)
        assert jnp.array_equal(engine.matmul(x, plan, cfg=cfg_p),
                               engine.matmul(x, plan, cfg=cfg_j))


def test_fused_kernel_matches_fused_ref():
    """Kernel-level check: scales threaded through the epilogue tile-wise
    equal the whole-array reference dequantization."""
    key = jax.random.PRNGKey(3)
    a = jax.random.randint(key, (2, 100, 300), -15, 16, dtype=jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (2, 300, 70), -15, 16,
                           dtype=jnp.int8)
    a_scale = jax.random.uniform(jax.random.fold_in(key, 2), (100, 1),
                                 minval=0.01, maxval=1.0)
    w_scale = jax.random.uniform(jax.random.fold_in(key, 3), (1, 70),
                                 minval=0.01, maxval=1.0)
    out = pim_matmul_fused_pallas(a, w, a_scale, w_scale, interpret=True)
    assert out.dtype == jnp.float32
    assert jnp.array_equal(out, pim_matmul_fused_ref(a, w, a_scale, w_scale))


def test_fused_bias_within_one_ulp():
    """The in-kernel bias add contracts to an FMA (single rounding); it
    must stay within 1 ulp of the eager two-step reference."""
    cfg = PimConfig()
    w = jax.random.normal(jax.random.PRNGKey(0), (96, 24))
    b = jax.random.normal(jax.random.PRNGKey(2), (24,))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 96))
    plan = prepare_weights(w, cfg)
    fused = engine.matmul(x, plan, cfg=cfg, bias=b)
    two_step = engine.matmul(x, plan, cfg=cfg) + b[None, :]
    np.testing.assert_allclose(np.asarray(fused), np.asarray(two_step),
                               rtol=1.5e-7, atol=1e-7)


def test_planned_weights_flow_through_jit_and_scan():
    """Plans are pytrees: vmapped programming + lax.scan execution (the
    serving stack's scan-over-layers shape) stays bit-exact."""
    cfg = PimConfig(weight_bits=8, act_bits=8)
    ws = jax.random.normal(jax.random.PRNGKey(0), (3, 64, 32))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    stacked = jax.vmap(lambda w: prepare_weights(w, cfg))(ws)

    @jax.jit
    def run(x, stacked):
        def body(c, plan):
            return c, engine.matmul(x, plan, cfg=cfg)
        return jax.lax.scan(body, 0, stacked)[1]

    ys = run(x, stacked)
    for i in range(3):
        ref = reference_quantized_matmul(x, prepare_weights(ws[i], cfg), cfg)
        assert jnp.array_equal(ys[i], ref)


def test_depthwise_engine_route_exact():
    """Grouped convs run the bit-sliced engine per channel: integer plane
    products + shift-and-add must equal the per-channel int oracle."""
    cfg = PimConfig(weight_bits=4, act_bits=4)
    cols = jax.random.normal(jax.random.PRNGKey(0), (50, 9, 12))
    w = jax.random.normal(jax.random.PRNGKey(1), (9, 12))
    plan = prepare_depthwise_weights(w, cfg)
    out = engine.matmul(cols, plan, cfg=cfg)
    # oracle: quantized int32 per-channel dot, dequantized
    w_q = quantize(w, bits=cfg.weight_bits, axis=(0,))
    a_q = quantize(cols, bits=cfg.act_bits, axis=(1,))
    acc = jnp.einsum("mkc,kc->mc", a_q.values.astype(jnp.int32),
                     w_q.values.astype(jnp.int32),
                     preferred_element_type=jnp.int32)
    ref = acc.astype(jnp.float32) * a_q.scale[:, 0, :] * w_q.scale
    assert jnp.array_equal(out, ref)


def test_cnn_depthwise_pim_regression():
    """mobilenet's depthwise stage under PIM no longer bypasses the
    engine: the depthwise output must equal the engine route applied to
    the layer's im2col patches (not a float einsum + output fake-quant)."""
    from repro.core.workloads import mobilenet
    from repro.models.cnn import cnn_forward, init_cnn
    layers = mobilenet(4, 8, width=0.25)[:2]   # stem conv + dw0
    params = init_cnn(layers, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3))
    cfg = PimConfig(weight_bits=8, act_bits=8)
    got = cnn_forward(params, layers, x, pim=cfg)
    # replay the two layers by hand through the engine
    from repro.models.cnn import _im2col
    spec0, spec1 = layers
    cols0 = _im2col(x, spec0)
    h = jax.nn.relu(engine.matmul(
        cols0, prepare_weights(params[spec0.name]["w"].reshape(-1,
                                                               spec0.out_c),
                               cfg), cfg=cfg, bias=params[spec0.name]["b"]))
    cols1 = _im2col(h, spec1)
    b, oh, ow, _ = cols1.shape
    cols1 = cols1.reshape(b, oh, ow, spec1.kh * spec1.kw, spec1.in_c)
    wd = params[spec1.name]["w"].reshape(spec1.kh * spec1.kw, spec1.in_c)
    # dw0 is the stack's last spec, so cnn_forward skips its ReLU
    ref = engine.matmul(
        cols1, prepare_depthwise_weights(wd, cfg), cfg=cfg) \
        + params[spec1.name]["b"]
    out_ref = jnp.mean(ref, axis=(1, 2))
    assert jnp.array_equal(got, out_ref)


def test_cnn_plans_reused_across_forwards():
    """plan_cnn_weights programs every layer once; forwards with the
    shared plans are bit-identical to planning inside the call."""
    from repro.core.workloads import mobilenet
    from repro.models.cnn import cnn_forward, init_cnn, plan_cnn_weights
    layers = mobilenet(4, 8, width=0.25)[:3]   # conv + depthwise + conv
    params = init_cnn(layers, jax.random.PRNGKey(0))
    cfg = PimConfig()
    plans = plan_cnn_weights(params, layers, cfg)
    assert set(plans) == {s.name for s in layers}
    x1 = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3))
    x2 = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 8, 3))
    for x in (x1, x2):
        assert jnp.array_equal(
            cnn_forward(params, layers, x, pim=cfg, plans=plans),
            cnn_forward(params, layers, x, pim=cfg))


def test_serve_throughput_metric_accounts_for_batch():
    """opima_tokens_per_s must report actual batch throughput, not the
    constant 1/latency the cancelled-units bug produced."""
    from repro.configs import get_config
    from repro.launch.serve import opima_lm_estimate
    cfg = get_config("qwen2.5-3b").reduced(num_layers=2, d_model=64)
    pim_cfg = PimConfig()
    for batch in (1, 4):
        est = opima_lm_estimate(cfg, batch=batch, prompt=16, gen=8,
                                pim=pim_cfg)
        latency_s = est["opima_latency_ms_per_token_batch"] / 1e3
        expected = batch * (16 + 8) / (latency_s * (16 + 8))
        assert est["opima_tokens_per_s"] == pytest.approx(expected)
    est1 = opima_lm_estimate(cfg, batch=1, prompt=16, gen=8, pim=pim_cfg)
    est4 = opima_lm_estimate(cfg, batch=4, prompt=16, gen=8, pim=pim_cfg)
    assert est4["opima_tokens_per_s"] == pytest.approx(
        4 * est1["opima_tokens_per_s"])


@pytest.mark.slow
def test_serve_real_pim_path_smoke():
    """End-to-end: planned-weight PIM execution through prefill + decode
    (projection matmuls on the engine), plus the emulate substrate."""
    from repro.launch.serve import serve
    res = serve("qwen3-4b", batch=1, prompt_len=8, gen=3, layers=1,
                d_model=32, pim=True)
    assert res["generated"].shape == (1, 3)
    assert res["opima_tokens_per_s"] > 0
    res_em = serve("qwen3-4b", batch=1, prompt_len=8, gen=3, layers=1,
                   d_model=32, pim=True, pim_substrate="emulate")
    assert res_em["generated"].shape == (1, 3)
