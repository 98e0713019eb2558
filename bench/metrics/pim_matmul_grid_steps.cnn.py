"""Grid steps the ``pim_matmul`` kernel runs per forward: the sum of
(Mp/bm) * (Np/bn) * (Kp/bk) over the calls the program logs
(``repro.core.pim.kernel_calls``) while its forward is traced, for
shapes only, at the cell's batch. Nothing is compiled or run. A program
whose call log holds no tiles reads nothing."""
from harness import cells


def read(run):
    try:
        from repro.core.pim import kernel_calls
    except ImportError:
        return None
    import jax
    import jax.numpy as jnp
    from repro.models.cnn import cnn_forward, init_cnn, plan_cnn_weights
    drv = cells.driver(run.cell)
    layers, pim = drv.layer_specs(run.config), drv.pim_config(run.config)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_cnn(layers, k), key)
    plans = jax.eval_shape(lambda p: plan_cnn_weights(p, layers, pim),
                           params)
    hw = run.config["image_size"]
    x = jax.ShapeDtypeStruct((int(run.traffic["batch"]), hw, hw, 3),
                             jnp.float32)
    calls = kernel_calls(
        lambda p, pl, x: cnn_forward(p, layers, x, pim=pim, plans=pl),
        params, plans, x)
    if not calls or not all(hasattr(c, "bm") for c in calls):
        return None
    return float(sum((c.mp // c.bm) * (c.np // c.bn) * (c.kp // c.bk)
                     for c in calls))
