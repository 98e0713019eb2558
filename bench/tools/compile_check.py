"""Compile a cell's programs for a described TPU v5e, without the chip.

  JAX_PLATFORMS=cpu python3 bench/tools/compile_check.py <cell>

Builds the cell's configuration and mix as shapes only and compiles, for
one chip of a described ``v5e:2x2`` topology, the programs a run would
compile: the jitted build of the weights (initializer and programming in
one call), and one decode step and one prefill at the mix's shapes. Prints
each program's ``memory_analysis()`` as one JSON line. What the TPU
compiler refuses, or a program that does not fit, fails here at no chip
time; a compile that passes says nothing about speed.
"""
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))
# the LM cells, kept out of BENCHMARK.json until a comparison proves them
sys.path.insert(0, str(ROOT / "bench" / "tests"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: getattr(m, k, None) for k in keys}


def lm_programs(cfg, mix, one_chip):
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp
    from repro.launch.serve import plan_params_for_pim
    from repro.models import lm
    from repro.models.lm import init_lm

    from harness import cells
    drv = cells.load_module(ROOT / "bench" / "drivers" / "lm.py", "drv_lm")
    mcfg = drv.model_config(cfg)
    pcfg = dataclasses.replace(drv.pim_config(cfg), interpret=False)
    place = lambda t: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        t)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def make(k, mcfg, pcfg):
        return plan_params_for_pim(init_lm(mcfg, k), pcfg)

    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    yield "build_params", make.lower(key, mcfg, pcfg)
    params = place(jax.eval_shape(lambda k: make(k, mcfg, pcfg), key))
    kv_dt = getattr(jnp, cfg["kv_cache_dtype"])
    slots, pad, max_len = mix["slots"], mix["prompt_pad"], mix["max_len"]
    cache = place(jax.eval_shape(
        lambda: lm.init_cache(mcfg, slots, max_len, dtype=kv_dt)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)

    def decode(p, c, t, i):
        logits, c = lm.decode_step(p, mcfg, c, t, i)
        return jnp.argmax(logits, -1), c

    yield "decode_step", jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, i32(slots, 1), i32(slots))

    def prefill(p, t, n):
        logits, c = lm.prefill(p, mcfg, {"tokens": t}, max_len=pad,
                               cache_dtype=kv_dt, logits_index=n - 1)
        return jnp.argmax(logits, -1), c

    yield f"prefill_{pad}", jax.jit(prefill).lower(params, i32(1, pad),
                                                   i32())


def cnn_programs(cfg, mix, one_chip):
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.models.cnn import cnn_forward, init_cnn, plan_cnn_weights

    from harness import cells
    drv = cells.load_module(ROOT / "bench" / "drivers" / "cnn.py", "drv_cnn")
    layers = drv.layer_specs(cfg)
    pim = dataclasses.replace(drv.pim_config(cfg), interpret=False)
    place = lambda t: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        t)

    def make(key):
        params = init_cnn(layers, key)
        return ({n: p["b"] for n, p in params.items()},
                plan_cnn_weights(params, layers, pim))

    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    yield "build_params", jax.jit(make).lower(key)
    biases, plans = place(jax.eval_shape(make, key))
    shapes = {n: p["w"].shape
              for n, p in jax.eval_shape(lambda k: init_cnn(layers, k),
                                         key).items()}

    def forward(biases, plans, x):
        params = {n: {"w": jnp.zeros(shapes[n]), "b": b}
                  for n, b in biases.items()}
        return cnn_forward(params, layers, x, pim=pim, plans=plans)

    hw = cfg["image_size"]
    x = jax.ShapeDtypeStruct((mix["batch"], hw, hw, 3), jnp.float32,
                             sharding=one_chip)
    yield f"forward_batch{mix['batch']}", jax.jit(forward).lower(
        biases, plans, x)


def main() -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import cellfiles
    from harness import runner
    cell = cellfiles.find(sys.argv[1])
    cfg, mix = runner.effective(cell, False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    programs = {"lm": lm_programs, "cnn": cnn_programs}[cfg["driver"]]
    for name, lowered in programs(cfg, mix, one_chip):
        t0 = time.perf_counter()
        compiled = lowered.compile()
        print(json.dumps({"cell": cell.name, "program": name,
                          "compile_s": round(time.perf_counter() - t0, 1),
                          **memory(compiled)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
