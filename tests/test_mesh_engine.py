"""Device-mesh plan sharding: bit-identity and serving token equality,
run in subprocesses with 4 forced host devices (XLA_FLAGS must be set
before jax initializes, so these tests shell out).

Comparisons are made within one compilation regime (jit-vs-jit or
eager-vs-eager): jit and eager runs of the *same unsharded* matmul
already differ at the ulp level (XLA fuses the float dequant multiply
chain differently under jit), so cross-regime comparison would test XLA
fusion, not sharding.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_PREAMBLE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro import engine
""")

_DENSE_SCRIPT = _PREAMBLE + textwrap.dedent("""
    mesh = jax.make_mesh((4,), ("model",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 96))
    w = jax.random.normal(jax.random.PRNGKey(1), (96, 64))
    b = jax.random.normal(jax.random.PRNGKey(2), (64,))
    f = jax.jit(lambda a, p: engine.matmul(a, p))
    for bits in (4, 8):
        for sub in ("exact-pallas", "exact-jnp"):
            cfg = engine.PimConfig(weight_bits=bits, act_bits=bits,
                                   substrate=sub)
            ref = engine.matmul(x, engine.program(w, cfg))
            refj = f(x, engine.program(w, cfg))
            for spec in ("col", "row"):
                plan = engine.program(w, cfg, mesh=mesh, spec=spec)
                assert plan.shard is not None and plan.shard.kind == spec
                got = engine.matmul(x, plan)
                assert np.array_equal(np.asarray(ref), np.asarray(got)), \\
                    f"eager {sub} w{bits} {spec}"
                gotj = f(x, plan)
                assert np.array_equal(np.asarray(refj), np.asarray(gotj)), \\
                    f"jit {sub} w{bits} {spec}"
            # bias rides the col split (sharded over the output axis)
            refb = engine.matmul(x, engine.program(w, cfg), bias=b)
            gotb = engine.matmul(
                x, engine.program(w, cfg, mesh=mesh, spec="col"), bias=b)
            assert np.array_equal(np.asarray(refb), np.asarray(gotb)), \\
                f"bias col {sub} w{bits}"
    # emulate: column split of the dequantized float matmul is exact
    cfg = engine.PimConfig(substrate="emulate")
    ref = engine.matmul(x, engine.program(w, cfg))
    got = engine.matmul(x, engine.program(w, cfg, mesh=mesh, spec="col"))
    assert np.array_equal(np.asarray(ref), np.asarray(got)), "emulate col"
    # analog dense splits share a global auto-ranged ADC: must refuse
    for spec in ("col", "row"):
        try:
            engine.program(w, engine.PimConfig(substrate="analog"),
                           mesh=mesh, spec=spec)
        except ValueError:
            pass
        else:
            raise AssertionError(f"analog {spec} split did not raise")
    print("dense_shard_ok")
""")

_EXPERT_SCRIPT = _PREAMBLE + textwrap.dedent("""
    mesh = jax.make_mesh((4,), ("model",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 96))
    xp = jax.random.normal(jax.random.PRNGKey(4), (8, 5, 96))
    we = jax.random.normal(jax.random.PRNGKey(3), (8, 96, 64))
    for bits in (4, 8):
        for sub in ("exact-pallas", "analog-pallas"):
            cfg = engine.PimConfig(weight_bits=bits, act_bits=bits,
                                   substrate=sub)
            ref = engine.matmul(x, engine.program(we, cfg, kind="experts"))
            plan = engine.program(we, cfg, kind="experts", mesh=mesh)
            assert plan.shard is not None and plan.shard.kind == "expert"
            got = engine.matmul(x, plan)
            assert np.array_equal(np.asarray(ref), np.asarray(got)), \\
                f"expert broadcast {sub} w{bits}"
            refp = engine.matmul(
                xp, engine.program(we, cfg, kind="experts"), paired=True)
            gotp = engine.matmul(xp, plan, paired=True)
            assert np.array_equal(np.asarray(refp), np.asarray(gotp)), \\
                f"expert paired {sub} w{bits}"
    print("expert_shard_ok")
""")

_PERSIST_SCRIPT = _PREAMBLE + textwrap.dedent("""
    import tempfile
    mesh = jax.make_mesh((4,), ("model",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 96))
    w = jax.random.normal(jax.random.PRNGKey(1), (96, 64))
    we = jax.random.normal(jax.random.PRNGKey(3), (8, 96, 64))
    cfg = engine.PimConfig()
    tree = {"a_dh": engine.program(w, cfg, mesh=mesh, spec="col"),
            "b_hd": engine.program(w, cfg, mesh=mesh, spec="row"),
            "moe_edf": engine.program(we, cfg, kind="experts", mesh=mesh),
            "plain": engine.program(w, cfg)}
    ref = {k: np.asarray(engine.matmul(x, p)) for k, p in tree.items()}
    with tempfile.TemporaryDirectory() as d:
        engine.save_plans(d, tree)
        # without a mesh the shard stamp is stripped; plans still execute
        got, _, _ = engine.load_plans(d)
        for k in tree:
            assert getattr(got[k], "shard", None) is None
            assert np.array_equal(ref[k],
                                  np.asarray(engine.matmul(x, got[k]))), k
        # with a mesh the saved split is re-placed
        got, _, _ = engine.load_plans(d, mesh=mesh)
        assert got["a_dh"].shard.kind == "col"
        assert got["b_hd"].shard.kind == "row"
        assert got["moe_edf"].shard.kind == "expert"
        assert got["plain"].shard is None
        for k in tree:
            assert np.array_equal(ref[k],
                                  np.asarray(engine.matmul(x, got[k]))), k
    print("persist_shard_ok")
""")

_SCHED_SCRIPT = _PREAMBLE + textwrap.dedent("""
    from repro.launch.serve import serve_continuous
    kw = dict(num_slots=4, num_requests=6, prompt_len=16, gen=8, layers=2,
              d_model=64, pim=True, arrival_rate=0.5, seed=0)
    r0 = serve_continuous("qwen2.5-3b", **kw)
    r1 = serve_continuous("qwen2.5-3b", mesh="2,2", **kw)
    t0 = {r["id"]: r["tokens"] for r in r0["requests"]}
    t1 = {r["id"]: r["tokens"] for r in r1["requests"]}
    assert t0.keys() == t1.keys()
    for k in t0:
        assert np.array_equal(t0[k], t1[k]), f"request {k} tokens differ"
    assert r1["mesh"] == "2,2"
    print("sched_mesh_ok", len(t0))
""")

_DATA_ONLY_SCRIPT = _PREAMBLE + textwrap.dedent("""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_serve_mesh
    from repro.launch.serve import serve_continuous
    mesh = make_serve_mesh("4,1")
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 96))
    w = jax.random.normal(jax.random.PRNGKey(1), (96, 64))
    f = jax.jit(lambda a, p: engine.matmul(a, p))
    cfg = engine.PimConfig()
    # a size-1 split axis still stamps the plan, so the Pallas kernel
    # runs under shard_map with the batch over "data"
    plan = engine.program(w, cfg, mesh=mesh, spec="row")
    assert plan.shard is not None and plan.shard.kind == "col"
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    assert np.array_equal(np.asarray(f(x, engine.program(w, cfg))),
                          np.asarray(f(xs, plan))), "data-only matmul"
    we = jax.random.normal(jax.random.PRNGKey(3), (4, 96, 64))
    experts = engine.program(we, cfg, kind="experts", mesh=mesh)
    assert experts.shard is not None and experts.shard.kind == "expert"
    analog = engine.program(w, engine.PimConfig(substrate="analog"),
                            mesh=mesh)
    assert analog.shard is None
    kw = dict(num_slots=4, num_requests=6, prompt_len=16, gen=8, layers=2,
              d_model=64, pim=True, arrival_rate=0.5, seed=0)
    t0 = {r["id"]: r["tokens"]
          for r in serve_continuous("qwen2.5-3b", **kw)["requests"]}
    t1 = {r["id"]: r["tokens"]
          for r in serve_continuous("qwen2.5-3b", mesh="4,1",
                                    **kw)["requests"]}
    assert t0.keys() == t1.keys()
    for k in t0:
        assert np.array_equal(t0[k], t1[k]), f"request {k} tokens differ"
    print("data_only_ok")
""")


def _run(script: str, timeout: int = 900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_dense_shard_bit_identity():
    proc = _run(_DENSE_SCRIPT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "dense_shard_ok" in proc.stdout


@pytest.mark.slow
def test_expert_shard_bit_identity():
    proc = _run(_EXPERT_SCRIPT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "expert_shard_ok" in proc.stdout


@pytest.mark.slow
def test_shard_persist_roundtrip():
    proc = _run(_PERSIST_SCRIPT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "persist_shard_ok" in proc.stdout


@pytest.mark.slow
def test_sharded_continuous_scheduler_token_equality():
    proc = _run(_SCHED_SCRIPT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "sched_mesh_ok" in proc.stdout


@pytest.mark.slow
def test_data_only_mesh_stamps_plans():
    proc = _run(_DATA_ONLY_SCRIPT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "data_only_ok" in proc.stdout
