"""The plain references re-derive the program's weights from the seed on
their own: at the rehearsal sizes, their codes and scales equal what the
program programs (the tests may import the program; the references do
not)."""
import numpy as np

import cellfiles
from harness import cells, runner, seeds


def test_lm_reference_draws_the_programs_weights():
    import jax
    cell = cellfiles.find("qwen2.5-3b.decode-batch")
    cfg, mix = runner.effective(cell, True)
    drv, ref = cells.driver(cell), cells.reference(cell)
    seed = -(2**40) - 3
    params = drv.build_params(drv.model_config(cfg), drv.pim_config(cfg),
                              seeds.jax_key(seed, seeds.WEIGHTS))
    dims = tuple(sorted(ref._dims(cfg, mix).items()))
    ks = jax.random.split(seeds.jax_key(seed, seeds.WEIGHTS), 8)
    np.testing.assert_array_equal(ref._embedding(ks[0], dims),
                                  params["embed_vd"])
    names = {"q": ("attn", "wq_dh"), "k": ("attn", "wk_dh"),
             "v": ("attn", "wv_dh"), "o": ("attn", "wo_hd"),
             "up": ("mlp", "wi_dh"), "gate": ("mlp", "wg_dh"),
             "down": ("mlp", "wo_hd")}
    for i, key in enumerate(jax.random.split(ks[1], cfg["num_hidden_layers"])):
        w = ref._layer_weights(key, dims)
        for n, (blk, leaf) in names.items():
            plan = params["layers"][blk][leaf]
            np.testing.assert_array_equal(w[n][0], plan.values[i])
            np.testing.assert_array_equal(w[n][1], plan.scale[i])


def test_cnn_reference_draws_the_programs_weights():
    import jax
    cell = cellfiles.find("resnet18-cifar100.batch1024")
    cfg, _ = runner.effective(cell, True)
    ref = cells.reference(cell)
    from repro.core.pim import PimConfig
    from repro.models.cnn import init_cnn, plan_cnn_weights
    layers = cells.driver(cell).layer_specs(cfg)
    key = seeds.jax_key(7, seeds.WEIGHTS)
    plans = plan_cnn_weights(init_cnn(layers, key), layers,
                             PimConfig(substrate="exact-jnp"))
    w = ref._weights(key, cfg)
    assert set(w) == set(plans)
    for n, plan in plans.items():
        np.testing.assert_array_equal(w[n][0], plan.values)
        np.testing.assert_array_equal(w[n][1], plan.scale)
