"""Fused Pallas analog-readout kernel (``analog-pallas`` substrate):
bit-parity with the whole-array jnp ``analog`` oracle on the
deterministic (``rng=None``) path across bit widths, odd shapes, and all
three plan types; kernel-level parity against the readout reference in
every jit context; statistical consistency of the threaded-key noise
path; and plan-persistence round-trips on the new substrate."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core.pim import DensePlan, PimConfig
from repro.kernels.analog_readout import ops as analog_ops
from repro.kernels.analog_readout.analog_readout import (
    analog_fullscale_pallas, analog_tiles)
from repro.kernels.analog_readout.ref import (analog_fullscale_ref,
                                              analog_readout_fused_ref)


def _cfg(substrate, wb=4, ab=4, **kw):
    return PimConfig(weight_bits=wb, act_bits=ab, substrate=substrate, **kw)


def _planes(key, pa, pw, m, k, n):
    a = jax.random.randint(key, (pa, m, k), -15, 16, dtype=jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (pw, k, n), -15, 16,
                           dtype=jnp.int8)
    a_s = jax.random.uniform(jax.random.fold_in(key, 2), (m, 1),
                             minval=0.01, maxval=1.0)
    w_s = jax.random.uniform(jax.random.fold_in(key, 3), (1, n),
                             minval=0.01, maxval=1.0)
    return a, w, a_s, w_s


# ---------------------------------------------------------------------------
# kernel-level parity vs the whole-array oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pa,pw,m,k,n", [
    (1, 1, 8, 32, 16),
    (2, 2, 100, 300, 70),      # ragged + multi-pair + multi-K-tile
    (1, 2, 5, 37, 3),          # odd everything, K not a chunk multiple
    (2, 1, 8, 1024, 256),      # deep K: several sequential K tiles
    (1, 1, 1, 5, 1),           # degenerate, K below one WDM chunk
    (1, 1, 33, 8, 129),        # K == chunk exactly
])
def test_analog_kernel_bit_exact_vs_ref(pa, pw, m, k, n):
    key = jax.random.PRNGKey(pa * 1000 + pw * 100 + m)
    a, w, a_s, w_s = _planes(key, pa, pw, m, k, n)
    out = analog_ops.analog_matmul_fused(a, w, a_s, w_s, chunk=8,
                                         adc_bits=5, interpret=True)
    ref = analog_readout_fused_ref(a, w, a_s, w_s, 8, 5)
    assert out.dtype == jnp.float32
    assert jnp.array_equal(out, ref)


def test_analog_kernel_bit_exact_in_any_jit_context():
    """The bit-parity contract must survive graph context: eager oracle,
    jitted oracle, and oracle nested inside a larger jit all agree with
    the kernel (the integer-code accumulation makes the arithmetic immune
    to XLA fast-math reassociation)."""
    key = jax.random.PRNGKey(7)
    a, w, a_s, w_s = _planes(key, 2, 2, 64, 192, 48)
    out = analog_ops.analog_matmul_fused(a, w, a_s, w_s, chunk=8,
                                         adc_bits=5, interpret=True)
    eager = analog_readout_fused_ref(a, w, a_s, w_s, 8, 5)
    jitted = jax.jit(
        lambda *z: analog_readout_fused_ref(*z, 8, 5))(a, w, a_s, w_s)
    nested = jax.jit(
        lambda *z: analog_readout_fused_ref(*z, 8, 5) * 1.0 + 0.0)(
            a, w, a_s, w_s)
    for ref in (eager, jitted, nested):
        assert jnp.array_equal(out, ref)


@pytest.mark.parametrize("chunk,adc_bits", [(4, 3), (8, 5), (16, 8)])
def test_analog_kernel_chunk_and_adc_sweep(chunk, adc_bits):
    key = jax.random.PRNGKey(chunk * 10 + adc_bits)
    a, w, a_s, w_s = _planes(key, 1, 1, 24, 100, 40)
    out = analog_ops.analog_matmul_fused(a, w, a_s, w_s, chunk=chunk,
                                         adc_bits=adc_bits, interpret=True)
    assert jnp.array_equal(
        out, analog_readout_fused_ref(a, w, a_s, w_s, chunk, adc_bits))


def test_fullscale_pass_matches_ref():
    """The auto-ranging pass (global max over pairs/chunks/rows/cols,
    accumulated across grid steps) is bit-identical to the whole-array
    reduction."""
    key = jax.random.PRNGKey(3)
    a, w, _, _ = _planes(key, 2, 2, 96, 272, 130)
    fs = analog_fullscale_pallas(a, w, None, chunk=8, interpret=True)
    assert jnp.array_equal(fs, analog_fullscale_ref(a, w, 8))


def test_analog_tiles_chunk_aligned():
    # tile edges always land on WDM-chunk boundaries (the wrapper then
    # pads K up to a bk multiple with whole zero chunks)
    for k in (8, 16, 304, 1024):
        _, _, bk = analog_tiles(100, k, 70, 8)
        assert bk % 8 == 0 and bk <= k
    with pytest.raises(AssertionError):
        analog_tiles(8, 37, 8, 8)   # k must arrive chunk-aligned


# ---------------------------------------------------------------------------
# engine-level parity: analog-pallas ≡ analog (rng=None), all plan types
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("wb,ab", [(4, 4), (8, 8)])
@pytest.mark.parametrize("m,k,n", [(16, 96, 40), (5, 37, 3), (8, 300, 70)])
def test_dense_substrate_parity(wb, ab, m, k, n):
    x = jax.random.normal(jax.random.PRNGKey(m + k), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(n), (k, n))
    ya = engine.matmul(x, engine.program(w, _cfg("analog", wb, ab)))
    yp = engine.matmul(x, engine.program(w, _cfg("analog-pallas", wb, ab)))
    assert jnp.array_equal(ya, yp)


def test_dense_parity_under_jit_with_bias():
    """Serving context: both substrates inside jit. The fused bias add may
    FMA-contract (like the exact kernel's), so bias parity is to 1 ulp."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    b = jax.random.normal(jax.random.PRNGKey(2), (32,))
    pa = engine.program(w, _cfg("analog"))
    pp = engine.program(w, _cfg("analog-pallas"))
    f = jax.jit(lambda x_, p: engine.matmul(x_, p))
    assert jnp.array_equal(f(x, pa), f(x, pp))
    ya = engine.matmul(x, pa, bias=b)
    yp = engine.matmul(x, pp, bias=b)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yp),
                               rtol=1e-6, atol=1e-6)


def test_depthwise_substrate_parity():
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 9, 12))
    w = jax.random.normal(jax.random.PRNGKey(1), (9, 12))
    ya = engine.matmul(x, engine.program(w, _cfg("analog"),
                                         kind="depthwise"))
    yp = engine.matmul(x, engine.program(w, _cfg("analog-pallas"),
                                         kind="depthwise"))
    assert jnp.array_equal(ya, yp)


@pytest.mark.parametrize("paired", [False, True])
def test_expert_substrate_parity(paired):
    e, m, k, n = 3, 4, 48, 24
    we = jax.random.normal(jax.random.PRNGKey(1), (e, k, n))
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (e, m, k) if paired else (m, k))
    ya = engine.matmul(x, engine.program(we, _cfg("analog"),
                                         kind="experts"), paired=paired)
    yp = engine.matmul(x, engine.program(we, _cfg("analog-pallas"),
                                         kind="experts"), paired=paired)
    assert ya.shape == (e, m, n)
    assert jnp.array_equal(ya, yp)


def test_analog_pallas_close_to_exact():
    """Sanity on fidelity, not just self-consistency: the deterministic
    5-bit readout stays within a few ADC steps of the exact datapath."""
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 32))
    y_exact = engine.matmul(x, engine.program(w, _cfg("exact-pallas")))
    y_analog = engine.matmul(x, engine.program(w, _cfg("analog-pallas")))
    # relative error bounded by ADC resolution (coarse — 5-bit codes)
    scale = float(jnp.max(jnp.abs(y_exact)))
    assert float(jnp.max(jnp.abs(y_analog - y_exact))) < 0.35 * scale
    corr = np.corrcoef(np.asarray(y_exact).ravel(),
                       np.asarray(y_analog).ravel())[0, 1]
    assert corr > 0.98


# ---------------------------------------------------------------------------
# noise path: threaded-key PRNG
# ---------------------------------------------------------------------------
def test_noise_requires_rng():
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    plan = engine.program(w, _cfg("analog-pallas", read_noise_sigma=0.05))
    with pytest.raises(ValueError, match="requires an rng key"):
        engine.matmul(x, plan)


def test_noise_reproducible_and_seed_dependent():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    plan = engine.program(w, _cfg("analog-pallas", read_noise_sigma=0.05))
    y0 = engine.matmul(x, plan, rng=jax.random.PRNGKey(5))
    y1 = engine.matmul(x, plan, rng=jax.random.PRNGKey(5))
    y2 = engine.matmul(x, plan, rng=jax.random.PRNGKey(6))
    assert jnp.array_equal(y0, y1)
    assert bool(jnp.any(y0 != y2))


@pytest.mark.slow
def test_noise_statistics_match_jnp_reference():
    """The kernel's per-tile threaded-key noise and the oracle's
    whole-array draw are different PRNG streams; their perturbation
    statistics around the deterministic readout must agree (mean ~ 0,
    matching std) over many keys."""
    sigma, keys = 0.05, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 192))
    w = jax.random.normal(jax.random.PRNGKey(1), (192, 32))
    det_plan = engine.program(w, _cfg("analog-pallas"))
    det = engine.matmul(x, det_plan)
    noisy_cfg_p = _cfg("analog-pallas", read_noise_sigma=sigma)
    noisy_cfg_a = _cfg("analog", read_noise_sigma=sigma)
    pp = engine.program(w, noisy_cfg_p)
    pa = engine.program(w, noisy_cfg_a)
    dev_p = jnp.stack([engine.matmul(x, pp, rng=jax.random.PRNGKey(s))
                       for s in range(keys)]) - det
    dev_a = jnp.stack([engine.matmul(x, pa, rng=jax.random.PRNGKey(s))
                       for s in range(keys)]) - det
    std_p, std_a = float(dev_p.std()), float(dev_a.std())
    assert abs(std_p - std_a) < 0.15 * max(std_p, std_a)
    assert abs(float(dev_p.mean())) < 0.1 * std_p
    assert abs(float(dev_a.mean())) < 0.1 * std_a


def test_noise_path_refuses_compiled_lowering():
    """The noise path has no Mosaic lowering: compiled, both passes
    refuse it by name instead of falling back to the interpreter."""
    a, w, a_s, w_s = _planes(jax.random.PRNGKey(0), 1, 1, 8, 64, 16)
    seed = jnp.int32(1)
    with pytest.raises(NotImplementedError, match="noise path"):
        analog_fullscale_pallas(a, w, seed, chunk=8, sigma=0.05,
                                interpret=False)
    with pytest.raises(NotImplementedError, match="noise path"):
        analog_ops.analog_readout_pallas(a, w, a_s, w_s, jnp.float32(1.0),
                                         seed, chunk=8, sigma=0.05,
                                         interpret=False)


# ---------------------------------------------------------------------------
# registry + persistence
# ---------------------------------------------------------------------------
def test_registered_and_not_exact():
    assert "analog-pallas" in engine.available_substrates()
    sub = engine.get_substrate("analog-pallas")
    assert not sub.is_exact and sub.integer_datapath


def test_plan_persistence_round_trip(tmp_path):
    cfg = _cfg("analog-pallas")
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 32))
    tree = {
        "dense": engine.program(
            jax.random.normal(jax.random.PRNGKey(2), (32, 16)), cfg),
        "experts": engine.program(
            jax.random.normal(jax.random.PRNGKey(4), (3, 32, 16)), cfg,
            kind="experts"),
    }
    d = str(tmp_path / "plans")
    engine.save_plans(d, tree)
    restored, _, _ = engine.load_plans(d)
    assert restored["dense"].cfg.resolved_substrate == "analog-pallas"
    assert jnp.array_equal(engine.matmul(x, tree["dense"]),
                           engine.matmul(x, restored["dense"]))
    assert jnp.array_equal(engine.matmul(x, tree["experts"]),
                           engine.matmul(x, restored["experts"]))
    # a restored analog-pallas plan re-routes to the jnp oracle and
    # still agrees bit-for-bit (same programming, same deterministic math)
    rerouted = engine.matmul(
        x, restored["dense"],
        cfg=dataclasses.replace(restored["dense"].cfg, substrate="analog"))
    assert jnp.array_equal(rerouted, engine.matmul(x, tree["dense"]))


def test_plan_prepadded_chunk_aligned():
    """Programming lands K on a WDM-chunk boundary, so neither analog
    route re-pads weights per call (the dedup contract with the exact
    path)."""
    w = jax.random.normal(jax.random.PRNGKey(0), (37, 3))
    plan = engine.program(w, _cfg("analog-pallas"))
    assert isinstance(plan, DensePlan)
    assert plan.planes.shape[1] % 8 == 0        # chunk-aligned
    assert plan.planes.shape[1] >= plan.k
    # exact substrates consume the same layout unchanged
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 37))
    exact_plan = engine.program(w, _cfg("exact-pallas"))
    assert exact_plan.planes.shape == plan.planes.shape
