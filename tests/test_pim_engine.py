"""PIM engine: bit-exactness of the nibble-sliced datapath vs the oracle,
and the analog readout model's error structure."""
import jax
import jax.numpy as jnp
import pytest
from hypo_compat import given, settings, st

from repro import engine
from repro.core.pim import (PimConfig, prepare_weights,
                            reference_quantized_matmul)


@pytest.mark.parametrize("wb,ab", [(4, 4), (8, 8), (8, 4), (4, 8), (2, 6)])
def test_exact_mode_bit_exact(wb, ab):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (16, 96))
    w = jax.random.normal(jax.random.PRNGKey(1), (96, 24))
    cfg = PimConfig(weight_bits=wb, act_bits=ab)
    wq = prepare_weights(w, cfg)
    assert jnp.array_equal(engine.matmul(x, wq, cfg=cfg),
                           reference_quantized_matmul(x, wq, cfg))


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 33), st.integers(1, 257), st.integers(1, 17),
       st.integers(0, 2 ** 30))
def test_exact_mode_bit_exact_shapes(m, k, n, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (k, n))
    cfg = PimConfig(weight_bits=8, act_bits=8)
    wq = prepare_weights(w, cfg)
    assert jnp.array_equal(engine.matmul(x, wq, cfg=cfg),
                           reference_quantized_matmul(x, wq, cfg))


def test_wraparound_large_k_exact():
    """int32 intermediate wraparound stays exact (doc'd modular argument)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8192))
    w = jax.random.normal(jax.random.PRNGKey(1), (8192, 8))
    cfg = PimConfig(weight_bits=8, act_bits=8)
    wq = prepare_weights(w, cfg)
    assert jnp.array_equal(engine.matmul(x, wq, cfg=cfg),
                           reference_quantized_matmul(x, wq, cfg))


def test_analog_error_decreases_with_adc_bits():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    errs = []
    for adc in (4, 5, 8):
        cfg = PimConfig(substrate="analog", adc_bits=adc,
                        read_noise_sigma=1e-9)
        wq = prepare_weights(w, cfg)
        y = engine.matmul(x, wq, cfg=cfg, rng=jax.random.PRNGKey(2))
        ref = reference_quantized_matmul(x, wq, cfg)
        errs.append(float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref)))
    assert errs[0] > errs[1] > errs[2]


def test_analog_noise_scales_with_sigma():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    outs = []
    for sigma in (1e-3, 5e-2):
        cfg = PimConfig(substrate="analog", adc_bits=8,
                        read_noise_sigma=sigma)
        wq = prepare_weights(w, cfg)
        y = engine.matmul(x, wq, cfg=cfg, rng=jax.random.PRNGKey(2))
        ref = reference_quantized_matmul(x, wq, cfg)
        outs.append(float(jnp.linalg.norm(y - ref)))
    assert outs[1] > outs[0]


def test_pallas_path_matches_jnp_path():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    cfg_j = PimConfig(weight_bits=8, act_bits=4, substrate="exact-jnp")
    cfg_p = PimConfig(weight_bits=8, act_bits=4, substrate="exact-pallas",
                      interpret=True)
    wq = prepare_weights(w, cfg_j)
    assert jnp.array_equal(engine.matmul(x, wq, cfg=cfg_j),
                           engine.matmul(x, wq, cfg=cfg_p))


def test_rejects_wide_operands():
    x = jnp.ones((2, 4))
    w = jnp.ones((4, 2))
    cfg = PimConfig(weight_bits=16, act_bits=8)
    with pytest.raises(NotImplementedError):
        engine.matmul(x, prepare_weights(w, PimConfig(weight_bits=8)),
                      cfg=cfg)
