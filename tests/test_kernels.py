"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret mode)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.pim_matmul.pim_matmul import pim_matmul_pallas
from repro.kernels.pim_matmul.ref import pim_matmul_ref
from repro.kernels.ssd_scan.ref import ssd_chunked_ref, ssd_scan_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas


@pytest.mark.parametrize("pa,pw,m,k,n", [
    (1, 1, 8, 32, 16),
    (2, 2, 128, 512, 128),     # MXU-aligned tile exactly
    (2, 1, 100, 300, 70),      # ragged -> padding path
    (1, 2, 8, 1024, 256),
    (2, 2, 1, 16, 1),          # degenerate
])
def test_pim_matmul_kernel_exact(pa, pw, m, k, n):
    key = jax.random.PRNGKey(pa * 1000 + pw * 100 + m)
    a = jax.random.randint(key, (pa, m, k), -15, 16, dtype=jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (pw, k, n), -15, 16,
                           dtype=jnp.int8)
    out = pim_matmul_pallas(a, w, interpret=True)
    assert out.dtype == jnp.int32
    assert jnp.array_equal(out, pim_matmul_ref(a, w))


@pytest.mark.parametrize("bm,bn,bk", [(32, 32, 64), (128, 128, 128)])
def test_pim_matmul_kernel_block_shapes(bm, bn, bk):
    key = jax.random.PRNGKey(7)
    a = jax.random.randint(key, (2, 96, 192), -15, 16, dtype=jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (2, 192, 64), -15, 16,
                           dtype=jnp.int8)
    out = pim_matmul_pallas(a, w, bm=bm, bn=bn, bk=bk, interpret=True)
    assert jnp.array_equal(out, pim_matmul_ref(a, w))


@pytest.mark.parametrize("m,k,n,with_bias", [
    (100, 300, 70, False),     # ragged
    (100, 300, 70, True),
    (128, 512, 128, False),    # tile-exact
    (1, 16, 1, True),          # degenerate
])
def test_fused_epilogue_tiled_scales_match_ref(m, k, n, with_bias):
    """The fused kernel, whose scales ride (SUBLANE, LANE) register tiles
    (what compiled Mosaic needs), matches the whole-array reference for
    ragged, tile-exact and degenerate shapes: bit for bit without a bias,
    and within the FMA's 1 ulp with one."""
    from repro.kernels.pim_matmul.pim_matmul import pim_matmul_fused_pallas
    from repro.kernels.pim_matmul.ref import pim_matmul_fused_ref
    key = jax.random.PRNGKey(m + n)
    a = jax.random.randint(key, (2, m, k), -15, 16, dtype=jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (2, k, n), -15, 16,
                           dtype=jnp.int8)
    a_s = jax.random.uniform(jax.random.fold_in(key, 2), (m, 1),
                             minval=0.01, maxval=1.0)
    w_s = jax.random.uniform(jax.random.fold_in(key, 3), (1, n),
                             minval=0.01, maxval=1.0)
    bias = jax.random.normal(jax.random.fold_in(key, 4), (1, n)) \
        if with_bias else None
    got = pim_matmul_fused_pallas(a, w, a_s, w_s, bias, interpret=True)
    want = pim_matmul_fused_ref(a, w, a_s, w_s, bias)
    assert got.shape == want.shape == (m, n)
    if with_bias:
        # the fused bias add is an FMA: it skips the rounding of the
        # product that the reference's mul-then-add makes, so the two
        # differ by at most an ulp of the product plus one of the sum
        prod = np.asarray(pim_matmul_fused_ref(a, w, a_s, w_s))
        want = np.asarray(want)
        tol = np.spacing(np.abs(prod)) + np.spacing(np.abs(want))
        assert (np.abs(np.asarray(got) - want) <= tol).all()
    else:
        assert jnp.array_equal(got, want)


def _check_tiles(m, kp, np_, pa, pw, want_rowsum=False):
    """The chooser's invariants at one issued (M, Kp, Np): the tiles
    divide Kp and Np and only the last M tile may be partial; every
    block's last two dims are multiples of (32, 128) for int8 and (8,
    128) for f32, or the whole dim; the blocks fit the VMEM budget."""
    from repro.kernels.pim_matmul import pim_matmul as pk
    bm, bn, bk = pk.kernel_tiles(m, kp, np_, pa, pw, want_rowsum)
    assert kp % bk == 0 and np_ % bn == 0, (bm, bn, bk)
    assert 0 < bm <= m and -(-m // bm) * bm - m < bm
    assert bm <= pk.MAX_BM and bn <= pk.MAX_BN
    aligned = lambda v, whole, step: v == whole or v % step == 0
    assert aligned(bm, m, 32) and aligned(bk, kp, 128)     # int8 planes
    assert aligned(bk, kp, 32) and aligned(bn, np_, 128)   # int8 weights
    assert aligned(bm, m, 8)                               # f32 out, scales
    assert pk.tile_vmem_bytes(bm, bn, bk, pa, pw, want_rowsum) <= \
        pk.TILE_BUDGET < pk.VMEM_LIMIT
    return bm, bn, bk


def _cell_calls(cell_name):
    """The kernel calls a CNN benchmark cell's forward logs, traced for
    shapes only at the cell's own batch."""
    import pathlib
    import sys
    bench = pathlib.Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    from harness import cells, runner

    from repro.core.pim import kernel_calls
    from repro.models.cnn import cnn_forward, init_cnn, plan_cnn_weights
    cell = cells.find(cell_name)
    cfg, mix = runner.effective(cell, False)
    drv = cells.driver(cell)
    layers, pim = drv.layer_specs(cfg), drv.pim_config(cfg)
    params = jax.eval_shape(lambda: init_cnn(layers, jax.random.PRNGKey(0)))
    plans = jax.eval_shape(lambda p: plan_cnn_weights(p, layers, pim),
                           params)
    hw = cfg["image_size"]
    x = jax.ShapeDtypeStruct((mix["batch"], hw, hw, 3), jnp.float32)
    return kernel_calls(
        lambda p, pl, x: cnn_forward(p, layers, x, pim=pim, plans=pl),
        params, plans, x)


@pytest.mark.parametrize("cell", ["resnet18-cifar100.batch1024",
                                  "vgg16-imagenette.batch64"])
def test_kernel_tiles_at_every_cnn_cell_call(cell):
    """Every call of the cell gets tiles that divide M too (no partial
    tile, so the issued work, and the pad share, is the plan's), and the
    whole forward runs on a few thousand grid steps."""
    calls = _cell_calls(cell)
    steps = 0
    for c in calls:
        bm, bn, bk = _check_tiles(c.m, c.kp, c.np, c.pa, c.pw)
        assert (c.bm, c.bn, c.bk) == (bm, bn, bk)
        assert c.mp == c.m and c.m % bm == 0
        steps += (c.m // bm) * (c.np // bn) * (c.kp // bk)
    assert 0 < steps < 10_000


@pytest.mark.parametrize("m", [8, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("bits", [4, 8], ids=["w4a4", "w8a8"])
@pytest.mark.parametrize("want_rowsum", [False, True],
                         ids=["plain", "rowsum"])
def test_kernel_tiles_at_lm_shapes(m, bits, want_rowsum):
    """qwen2.5-3b's MLP up/gate projection (K 2048, N 11008): a small M
    keeps its whole M and K in one tile, and N splits into 256-column
    tiles (11008 is 86 lanes of 128)."""
    from repro.kernels.pim_matmul.pim_matmul import plan_padding
    kp, np_ = plan_padding(2048, 11008)
    planes = bits // 4
    assert _check_tiles(m, kp, np_, planes, planes, want_rowsum) == \
        (m, 256, 2048)


@pytest.mark.parametrize("m,with_bias,want_rowsum", [
    (4096, False, False),
    (4096, True, False),
    (4096, False, True),
    (4100, True, True),        # the last M tile partial
])
def test_fused_kernel_bit_identity_at_chosen_tiles(m, with_bias,
                                                   want_rowsum):
    """At K 1152 (Kp 1536) and N 64 the chooser takes several M tiles and
    one whole-K step. The result is bit-identical to the same kernel on
    128 x 64 x 512 tiles (three K steps), and to the whole-array
    reference but for the fused bias add: an FMA there, one rounding of
    the product fewer."""
    from repro.kernels.pim_matmul.pim_matmul import (kernel_tiles,
                                                     pim_matmul_fused_pallas,
                                                     plan_padding)
    from repro.kernels.pim_matmul.ref import pim_matmul_fused_ref
    k, n = 1152, 64
    bm, _, bk = kernel_tiles(m, *plan_padding(k, n))
    assert m > bm and bk == plan_padding(k, n)[0]
    key = jax.random.PRNGKey(m)
    a = jax.random.randint(key, (1, m, k), -8, 8, dtype=jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (1, k, n), -8, 8,
                           dtype=jnp.int8)
    a_s = jax.random.uniform(jax.random.fold_in(key, 2), (m, 1),
                             minval=0.01, maxval=1.0)
    w_s = jax.random.uniform(jax.random.fold_in(key, 3), (1, n),
                             minval=0.01, maxval=1.0)
    bias = jax.random.normal(jax.random.fold_in(key, 4), (1, n)) \
        if with_bias else None
    run = functools.partial(pim_matmul_fused_pallas, a, w, a_s, w_s, bias,
                            interpret=True, want_rowsum=want_rowsum)
    got, steps = run(), run(bm=128, bk=512)
    ref = pim_matmul_fused_ref(a, w, a_s, w_s, bias, want_rowsum=want_rowsum)
    for x, y, z in zip(*(jax.tree.leaves(t) for t in (got, steps, ref))):
        assert jnp.array_equal(x, y)
        if with_bias and x.dtype == jnp.float32:
            prod = np.abs(np.asarray(pim_matmul_fused_ref(a, w, a_s, w_s)))
            tol = np.spacing(prod) + np.spacing(np.abs(np.asarray(z)))
            assert np.all(np.abs(np.asarray(x) - np.asarray(z)) <= tol)
        else:
            assert jnp.array_equal(x, z)


@pytest.mark.parametrize("bh,l,p,n,q", [
    (2, 128, 16, 8, 32),
    (1, 64, 8, 128, 64),
    (3, 96, 32, 16, 32),
    (1, 32, 64, 64, 32),
])
def test_ssd_kernel_matches_sequential(bh, l, p, n, q):
    ks = jax.random.split(jax.random.PRNGKey(bh * l), 4)
    x = jax.random.normal(ks[0], (bh, l, p))
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (bh, l)) + 2.0)
    b = jax.random.normal(ks[2], (bh, l, n)) / np.sqrt(n)
    c = jax.random.normal(ks[3], (bh, l, n)) / np.sqrt(n)
    y_ref, s_ref = ssd_scan_ref(x, a, b, c)
    y_ker, s_ker = ssd_scan_pallas(x, a, b, c, chunk=q, interpret=True)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_ker), np.asarray(s_ref),
                               rtol=2e-4, atol=2e-5)


def test_ssd_chunked_jnp_matches_sequential():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (2, 256, 32))
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (2, 256)) + 2.0)
    b = jax.random.normal(ks[2], (2, 256, 16)) / 4.0
    c = jax.random.normal(ks[3], (2, 256, 16)) / 4.0
    y_ref, s_ref = ssd_scan_ref(x, a, b, c)
    for chunk in (32, 64, 128, 256):
        y, s = ssd_chunked_ref(x, a, b, c, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-5)


def test_ssd_kernel_long_decay_stability():
    """Near-zero decays (long-range forgetting) stay finite in log-space."""
    bh, l, p, n = 1, 64, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (bh, l, p))
    a = jnp.full((bh, l), 1e-6)
    b = jax.random.normal(ks[1], (bh, l, n))
    c = jax.random.normal(ks[2], (bh, l, n))
    y, s = ssd_scan_pallas(x, a, b, c, chunk=32, interpret=True)
    assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(s)))


@pytest.mark.parametrize("b,s,h,kv,d,causal,win,pre", [
    (2, 128, 4, 2, 32, True, 0, 0),
    (1, 128, 8, 1, 16, True, 0, 0),      # MQA
    (2, 64, 4, 4, 32, False, 0, 0),      # bidirectional (encoder)
    (1, 128, 4, 2, 16, True, 40, 0),     # sliding window
    (1, 128, 4, 2, 16, True, 0, 24),     # prefix-LM
    (1, 128, 4, 2, 16, True, 24, 16),    # window + prefix
])
def test_flash_attention_kernel(b, s, h, kv, d, causal, win, pre):
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_pallas
    from repro.kernels.flash_attention.ref import flash_attention_ref
    ks = jax.random.split(jax.random.PRNGKey(s + h + d), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, d), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal, win, pre, bq=32, bk=32,
                                 interpret=True)
    ref = flash_attention_ref(q, k, v, causal, win, pre)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_pallas
    from repro.kernels.flash_attention.ref import flash_attention_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 64, 2, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 64, 2, 32), jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, bq=32, bk=32, interpret=True)
    ref = flash_attention_ref(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)
