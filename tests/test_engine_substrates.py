"""Engine substrate registry: parity of the exact substrates with the
integer oracle (dense / depthwise / expert-stacked at w4a4 and w8a8),
analog tolerance, emulate semantics, registry behavior (unknown-substrate
errors), the planes verb, and plan persistence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core.pim import (DensePlan, DepthwisePlan, ExpertStackedPlan,
                            PimConfig, plan_from_qtensor, prepare_weights,
                            quantize_activations,
                            reference_quantized_matmul)
from repro.quant.quantize import fake_quantize, quantize

EXACT_SUBSTRATES = ("exact-pallas", "exact-jnp")
BITS = ((4, 4), (8, 8))


def _cfg(substrate, wb=4, ab=4, **kw):
    return PimConfig(weight_bits=wb, act_bits=ab, substrate=substrate, **kw)


# ---------------------------------------------------------------------------
# exact-substrate parity vs the un-sliced integer oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("wb,ab", BITS)
@pytest.mark.parametrize("substrate", EXACT_SUBSTRATES)
def test_dense_parity_bit_exact(substrate, wb, ab):
    cfg = _cfg(substrate, wb, ab)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 96))
    w = jax.random.normal(jax.random.PRNGKey(1), (96, 40))
    plan = engine.program(w, cfg)
    assert isinstance(plan, DensePlan)
    assert plan.substrate == substrate
    ref = reference_quantized_matmul(x, plan, cfg)
    assert jnp.array_equal(engine.matmul(x, plan), ref)


@pytest.mark.parametrize("wb,ab", BITS)
def test_dense_substrates_agree_bit_exact(wb, ab):
    """exact-pallas ≡ exact-jnp on the same programmed codes."""
    x = jax.random.normal(jax.random.PRNGKey(0), (33, 200))
    w = jax.random.normal(jax.random.PRNGKey(1), (200, 72))
    outs = [engine.matmul(x, engine.program(w, _cfg(s, wb, ab)))
            for s in EXACT_SUBSTRATES]
    assert jnp.array_equal(outs[0], outs[1])


@pytest.mark.parametrize("wb,ab", BITS)
@pytest.mark.parametrize("substrate", EXACT_SUBSTRATES)
def test_depthwise_parity_bit_exact(substrate, wb, ab):
    cfg = _cfg(substrate, wb, ab)
    cols = jax.random.normal(jax.random.PRNGKey(0), (50, 9, 12))
    w = jax.random.normal(jax.random.PRNGKey(1), (9, 12))
    plan = engine.program(w, cfg, kind="depthwise")
    assert isinstance(plan, DepthwisePlan)
    out = engine.matmul(cols, plan)
    # oracle: quantized int32 per-channel dot, dequantized
    w_q = quantize(w, bits=wb, axis=(0,))
    a_q = quantize(cols, bits=ab, axis=(1,))
    acc = jnp.einsum("mkc,kc->mc", a_q.values.astype(jnp.int32),
                     w_q.values.astype(jnp.int32),
                     preferred_element_type=jnp.int32)
    ref = acc.astype(jnp.float32) * a_q.scale[:, 0, :] * w_q.scale
    assert jnp.array_equal(out, ref)


@pytest.mark.parametrize("wb,ab", BITS)
@pytest.mark.parametrize("substrate", EXACT_SUBSTRATES)
def test_expert_stacked_parity_bit_exact(substrate, wb, ab):
    cfg = _cfg(substrate, wb, ab)
    x = jax.random.normal(jax.random.PRNGKey(0), (10, 64))
    we = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 24))
    plan = engine.program(we, cfg, kind="experts")
    assert isinstance(plan, ExpertStackedPlan)
    assert plan.num_experts == 4 and plan.shape == (4, 64, 24)
    out = engine.matmul(x, plan)                 # broadcast -> (E, T, N)
    ref = jnp.stack([reference_quantized_matmul(
        x, prepare_weights(we[i], cfg), cfg) for i in range(4)])
    assert jnp.array_equal(out, ref)


def test_expert_stacked_paired_inputs():
    """paired=True pairs a leading expert axis on x with the experts (the
    MoE down-projection shape); pairing is explicit, never shape-inferred,
    so a broadcast batch equal to E cannot silently pair."""
    cfg = _cfg("exact-jnp")
    xe = jax.random.normal(jax.random.PRNGKey(0), (3, 10, 32))
    we = jax.random.normal(jax.random.PRNGKey(1), (3, 32, 16))
    plan = engine.program(we, cfg, kind="experts")
    out = engine.matmul(xe, plan, paired=True)
    ref = jnp.stack([reference_quantized_matmul(
        xe[i], prepare_weights(we[i], cfg), cfg) for i in range(3)])
    assert jnp.array_equal(out, ref)
    # without paired=True the same x broadcasts: every expert sees all of
    # xe, giving (E, E, T, N)
    assert engine.matmul(xe, plan).shape == (3, 3, 10, 16)


# ---------------------------------------------------------------------------
# analog / emulate semantics
# ---------------------------------------------------------------------------
def test_analog_within_tolerance():
    cfg = _cfg("analog", adc_bits=8, read_noise_sigma=1e-3)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    plan = engine.program(w, cfg)
    ref = reference_quantized_matmul(x, plan, cfg)
    y = engine.matmul(x, plan, rng=jax.random.PRNGKey(2))
    rel = float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))
    assert 0.0 < rel < 0.05, rel
    # an explicitly requested noise level must not silently vanish
    with pytest.raises(ValueError, match="requires an rng key"):
        engine.matmul(x, plan)
    # with the implied default sigma, rng=None is the deterministic
    # (ADC-only) readout — the serving route
    plan0 = engine.program(w, _cfg("analog", adc_bits=8))
    y0 = engine.matmul(x, plan0)
    assert jnp.array_equal(y0, engine.matmul(x, plan0))


def test_emulate_matches_fake_quantize():
    """The emulate substrate is serve.py's old fake-quantize escape hatch:
    float matmul against quantize-dequantized weights."""
    cfg = _cfg("emulate")
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 48))
    w = jax.random.normal(jax.random.PRNGKey(1), (48, 24))
    plan = engine.program(w, cfg)
    np.testing.assert_allclose(
        np.asarray(engine.matmul(x, plan)),
        np.asarray(x @ fake_quantize(w, cfg.weight_bits, axis=(0,))),
        rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# registry behavior
# ---------------------------------------------------------------------------
def test_unknown_substrate_raises():
    with pytest.raises(ValueError, match="unknown PIM substrate"):
        engine.get_substrate("optical-unobtainium")
    w = jnp.ones((4, 4))
    with pytest.raises(ValueError, match="unknown PIM substrate"):
        engine.program(w, _cfg("optical-unobtainium"))


def test_unknown_plan_kind_raises():
    with pytest.raises(ValueError, match="unknown plan kind"):
        engine.program(jnp.ones((4, 4)), _cfg("exact-jnp"), kind="sparse")


def test_available_substrates_complete():
    subs = engine.available_substrates()
    assert set(subs) >= {"exact-pallas", "exact-jnp", "analog", "emulate"}
    for name in subs:
        assert engine.get_substrate(name).name == name
    assert engine.get_substrate("exact-pallas").is_exact
    assert engine.get_substrate("exact-jnp").is_exact
    assert not engine.get_substrate("analog").is_exact
    assert not engine.get_substrate("emulate").is_exact


def test_register_substrate_round_trip():
    class Custom(engine.ExactJnpSubstrate):
        name = "test-custom"
    engine.register_substrate(Custom())
    try:
        assert "test-custom" in engine.available_substrates()
        cfg = _cfg("test-custom")
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
        w = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
        plan = engine.program(w, cfg)
        assert jnp.array_equal(engine.matmul(x, plan),
                               reference_quantized_matmul(x, plan, cfg))
    finally:
        engine.substrates._REGISTRY.pop("test-custom", None)


def test_cfg_override_must_match_plan_bits():
    """A route-override cfg cannot silently reinterpret the programmed
    weight width (the plan's codes were decomposed at plan.bits)."""
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    plan = engine.program(w, _cfg("exact-pallas", 8, 8))
    with pytest.raises(ValueError, match="programmed at 8 bits"):
        # a fresh default cfg carries weight_bits=4 — the quickstart-style
        # footgun this guard exists for
        engine.matmul(x, plan, cfg=PimConfig(substrate="exact-jnp"))
    ok = engine.matmul(
        x, plan, cfg=dataclasses.replace(plan.cfg, substrate="exact-jnp"))
    assert jnp.array_equal(ok, engine.matmul(x, plan))


def test_legacy_qtensor_adoption_keeps_bit_width():
    """Adopting non-default-width QTensor codes with plan_from_qtensor
    stamps the plan cfg with the codes' width, so engine.matmul on the
    plan passes the override-bits guard and matches the oracle."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 48))
    w = jax.random.normal(jax.random.PRNGKey(1), (48, 16))
    w_q = quantize(w, bits=8, axis=(0,))
    plan = plan_from_qtensor(w_q, PimConfig())
    assert plan.bits == plan.cfg.weight_bits == 8
    out = engine.matmul(x, plan)
    cfg8 = PimConfig(weight_bits=8)
    ref = reference_quantized_matmul(x, w_q, cfg8)
    assert jnp.array_equal(out, ref)


def test_emulate_supports_wide_operands():
    """The float-only emulate route serves bit widths above the int32
    datapath's 8-bit limit."""
    cfg = _cfg("emulate", 16, 16)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    out = engine.matmul(x, engine.program(w, cfg))
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(x @ fake_quantize(w, 16, axis=(0,))),
        rtol=1e-5, atol=1e-5)
    # the integer substrates still refuse wide operands
    with pytest.raises(NotImplementedError):
        engine.matmul(x, engine.program(w, _cfg("exact-jnp", 16, 16)))


def test_tree_fingerprint_distinguishes_containers():
    from repro.checkpoint.ckpt import tree_fingerprint
    a, b = jnp.ones((2,)), jnp.zeros((3,))
    assert tree_fingerprint({"0": a, "1": b}) != tree_fingerprint([a, b])
    assert tree_fingerprint({"x": a}) != tree_fingerprint({"y": a})
    assert tree_fingerprint({"x": a}) == tree_fingerprint({"x": b * 0 + 1})


def test_substrate_stamped_into_plan_cfg():
    """program() stamps the substrate so matmul needs no flags; an
    explicit cfg override still re-routes the same plan."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    plan = engine.program(w, PimConfig(weight_bits=8, act_bits=8),
                          substrate="exact-pallas")
    assert plan.cfg.substrate == "exact-pallas"
    rerouted = engine.matmul(
        x, plan, cfg=dataclasses.replace(plan.cfg, substrate="exact-jnp"))
    assert jnp.array_equal(engine.matmul(x, plan), rerouted)


@pytest.mark.parametrize("substrate",
                         ("exact-pallas", "exact-jnp", "analog",
                          "analog-pallas"))
def test_matmul_planes_equals_matmul(substrate):
    """engine.matmul_planes on the planes quantize_activations makes is
    engine.matmul, bit for bit, on every integer substrate."""
    cfg = _cfg(substrate)
    x = jax.random.normal(jax.random.PRNGKey(0), (12, 40))
    b = jax.random.normal(jax.random.PRNGKey(2), (24,))
    plan = engine.program(jax.random.normal(jax.random.PRNGKey(1),
                                            (40, 24)), cfg)
    ap, a_scale = quantize_activations(x, plan, cfg)
    assert ap.shape == (cfg.act_planes, 12, plan.planes.shape[1])
    np.testing.assert_array_equal(
        engine.matmul_planes(ap, a_scale, plan, bias=b),
        engine.matmul(x, plan, bias=b))


def test_matmul_planes_refuses_what_it_cannot_drive():
    """matmul_planes serves unsharded DensePlans on an integer datapath
    only: a plan split over a mesh, a float route, a depthwise plan and
    planes at another K all raise ValueError."""
    from jax.sharding import Mesh
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 40))
    w = jax.random.normal(jax.random.PRNGKey(1), (40, 24))
    plan = engine.program(w, _cfg("exact-jnp"))
    ap, a_scale = quantize_activations(x, plan, plan.cfg)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("model",))
    sharded = engine.program(w, _cfg("exact-jnp"), mesh=mesh, spec="col")
    assert sharded.shard is not None
    with pytest.raises(ValueError, match="split over a mesh"):
        engine.matmul_planes(ap, a_scale, sharded)
    with pytest.raises(ValueError, match="no integer datapath"):
        engine.matmul_planes(ap, a_scale, engine.program(w, _cfg("emulate")))
    dw = engine.program(jnp.ones((9, 8)), _cfg("exact-jnp"),
                        kind="depthwise")
    with pytest.raises(ValueError, match="DepthwisePlan"):
        engine.matmul_planes(ap, a_scale, dw)
    with pytest.raises(ValueError, match="deep"):
        engine.matmul_planes(jnp.pad(ap, ((0, 0), (0, 0), (0, 8))),
                             a_scale, plan)


def test_core_imports_nothing_from_engine():
    """repro.core is the math layer under the engine: no module in it
    imports repro.engine, so the package graph has no core -> engine ->
    core cycle."""
    import ast
    import pathlib
    import repro.core
    root = pathlib.Path(repro.core.__file__).parent
    found = []
    for path in sorted(root.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [
                    f"{node.module}.{a.name}" for a in node.names]
            else:
                continue
            found += [(path.name, n) for n in names
                      if n == "repro.engine" or n.startswith("repro.engine.")]
    assert len(list(root.rglob("*.py"))) > 5
    assert found == []


# ---------------------------------------------------------------------------
# plan persistence
# ---------------------------------------------------------------------------
def test_plan_persistence_round_trip(tmp_path):
    cfg = _cfg("exact-pallas", 4, 4)
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 32))
    cols = jax.random.normal(jax.random.PRNGKey(1), (6, 9, 8))
    tree = {
        "dense": engine.program(
            jax.random.normal(jax.random.PRNGKey(2), (32, 16)), cfg),
        "dw": engine.program(
            jax.random.normal(jax.random.PRNGKey(3), (9, 8)), cfg,
            kind="depthwise"),
        "experts": engine.program(
            jax.random.normal(jax.random.PRNGKey(4), (3, 32, 16)), cfg,
            kind="experts"),
        "aux": {"table": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
    }
    d = str(tmp_path / "plans")
    engine.save_plans(d, tree, extras={"note": "unit-test"})
    restored, step, extras = engine.load_plans(d)
    assert step == 0 and extras["note"] == "unit-test"
    # manifest extras record substrate + full PimConfig per plan
    import json, os
    with open(os.path.join(d, "step_00000000", "manifest.json")) as f:
        spec = json.load(f)["extras"]["engine_plans"]
    assert spec["items"]["dense"]["cfg"]["substrate"] == "exact-pallas"
    assert spec["items"]["dense"]["cfg"]["weight_bits"] == 4
    # restored plans execute bit-identically
    assert jnp.array_equal(engine.matmul(x, tree["dense"]),
                           engine.matmul(x, restored["dense"]))
    assert jnp.array_equal(engine.matmul(cols, tree["dw"]),
                           engine.matmul(cols, restored["dw"]))
    assert jnp.array_equal(engine.matmul(x, tree["experts"]),
                           engine.matmul(x, restored["experts"]))
    np.testing.assert_array_equal(np.asarray(tree["aux"]["table"]),
                                  np.asarray(restored["aux"]["table"]))


def test_load_plans_missing_and_unspecced(tmp_path):
    with pytest.raises(FileNotFoundError):
        engine.load_plans(str(tmp_path / "nope"))
    # a checkpoint not written by save_plans has no plan spec
    from repro.checkpoint.ckpt import save_checkpoint
    d = str(tmp_path / "plain")
    save_checkpoint(d, 0, {"w": jnp.ones((2, 2))})
    with pytest.raises(ValueError, match="engine_plans"):
        engine.load_plans(d)


def test_manifest_with_retired_cfg_keys_loads(tmp_path):
    """Manifests written while PimConfig still had ``analog``,
    ``use_pallas`` and ``cell_bits`` carry them in every plan's cfg. The
    loader drops exactly those three; any other unknown key still
    fails."""
    import json
    import os
    cfg = _cfg("exact-jnp", 4, 4)
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 32))
    cols = jax.random.normal(jax.random.PRNGKey(1), (6, 9, 8))
    tree = {"dense": engine.program(
                jax.random.normal(jax.random.PRNGKey(2), (32, 16)), cfg),
            "dw": engine.program(
                jax.random.normal(jax.random.PRNGKey(3), (9, 8)), cfg,
                kind="depthwise")}
    d = str(tmp_path / "plans")
    engine.save_plans(d, tree)
    path = os.path.join(d, "step_00000000", "manifest.json")

    def rewrite(extra):
        with open(path) as f:
            manifest = json.load(f)
        for item in manifest["extras"]["engine_plans"]["items"].values():
            item["cfg"].update(extra)
        with open(path, "w") as f:
            json.dump(manifest, f)

    rewrite({"analog": False, "use_pallas": True, "cell_bits": 4})
    restored, _, _ = engine.load_plans(d)
    assert restored["dense"].cfg == tree["dense"].cfg
    assert jnp.array_equal(engine.matmul(x, restored["dense"]),
                           engine.matmul(x, tree["dense"]))
    assert jnp.array_equal(engine.matmul(cols, restored["dw"]),
                           engine.matmul(cols, tree["dw"]))
    rewrite({"adc_gain": 2.0})
    with pytest.raises(TypeError, match="adc_gain"):
        engine.load_plans(d)


def test_checkpoint_treedef_fingerprint_validated(tmp_path):
    """Same leaf count + shapes but different container keys must be
    rejected on restore (the dead `if False` fingerprint never was)."""
    from repro.checkpoint.ckpt import restore_checkpoint, save_checkpoint
    d = str(tmp_path / "ckpt")
    tree = {"a": jnp.ones((2, 3)), "b": jnp.zeros((4,))}
    save_checkpoint(d, 1, tree)
    restored, _, _ = restore_checkpoint(d, tree)     # matching template ok
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.ones((2, 3), np.float32))
    bad = {"a": jnp.ones((2, 3)), "c": jnp.zeros((4,))}
    with pytest.raises(ValueError, match="structure mismatch"):
        restore_checkpoint(d, bad)


# ---------------------------------------------------------------------------
# serving integration: substrates reachable through plan_params_for_pim
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("substrate",
                         ("exact-pallas", "exact-jnp", "analog", "emulate"))
def test_plan_params_program_all_substrates(substrate):
    """Every registered substrate is reachable from the serving planner:
    projections become DensePlans and MoE expert stacks become
    ExpertStackedPlans stamped with the requested substrate."""
    from repro.configs import get_config
    from repro.launch.serve import plan_params_for_pim
    from repro.models.lm import init_lm
    cfg = get_config("qwen3-moe-30b-a3b").reduced(num_layers=1, d_model=32,
                                                  vocab=64)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    pim_cfg = _cfg(substrate)
    planned = plan_params_for_pim(params, pim_cfg)
    attn = planned["layers"]["attn"]
    assert isinstance(attn["wq_dh"], DensePlan)
    assert attn["wq_dh"].cfg.substrate == substrate
    moe = planned["layers"]["moe"]
    assert isinstance(moe["wi_edf"], ExpertStackedPlan)
    assert isinstance(moe["wo_efd"], ExpertStackedPlan)
    assert moe["wi_edf"].cfg.substrate == substrate
    # router stays digital (float), embeddings stay fake-quantized arrays
    assert not isinstance(moe["router_de"], engine.Plan)
    assert not isinstance(planned["embed_vd"], engine.Plan)


@pytest.mark.slow
def test_serve_moe_experts_on_engine():
    """--pim on a MoE arch decodes with expert stacks on the real engine
    (the ROADMAP _edf/_efd gap)."""
    from repro.launch.serve import serve
    res = serve("qwen3-moe-30b-a3b", batch=1, prompt_len=8, gen=2, layers=1,
                d_model=32, pim=True)
    assert res["generated"].shape == (1, 2)
    assert res["pim_substrate"] == "exact-pallas"


@pytest.mark.slow
def test_serve_plan_dir_restart_identical(tmp_path):
    """A restart restoring persisted plans generates identical tokens."""
    from repro.launch.serve import serve
    d = str(tmp_path / "plans")
    res1 = serve("qwen2.5-3b", batch=1, prompt_len=8, gen=2, layers=1,
                 d_model=32, pim=True, plan_dir=d)
    res2 = serve("qwen2.5-3b", batch=1, prompt_len=8, gen=2, layers=1,
                 d_model=32, pim=True, plan_dir=d)
    np.testing.assert_array_equal(res1["generated"], res2["generated"])
    # a checkpoint programmed for a different operating point is stale:
    # serving must re-program (and re-save) instead of silently reusing it
    serve("qwen2.5-3b", batch=1, prompt_len=8, gen=2, layers=1,
          d_model=32, pim=True, pim_bits=8, plan_dir=d)
    _, _, extras = engine.load_plans(d)
    assert extras["weight_bits"] == 8
    # ...including a different model geometry (used to restore stale
    # plans and crash deep in attention)
    res4 = serve("qwen2.5-3b", batch=1, prompt_len=8, gen=2, layers=1,
                 d_model=48, pim=True, plan_dir=d)
    assert res4["generated"].shape == (1, 2)
    assert engine.load_plans(d)[2]["d_model"] == 48
