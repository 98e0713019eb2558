"""Where a served LM and its plain reference part, layer by layer.

  python3 bench/tools/diverge.py --workload <lm cell> --seed <n> [--rehearse]

Serves one request through the cell's engine as a run sets it up
(prefill, insert, then single decode steps), reads every layer's keys and
values back from the slot cache, and compares them bit for bit with the
reference's cache of the same request at each position, prompt rows and
decoded rows apart: the first layer and rows that differ point at the
operation. Layer 0's decoded rows are also computed by the program's own
functions jitted apart, which tells the reference's arithmetic from the
way the engine's decode program is compiled. Prints one JSON line per
finding; the last one holds the reference's readings of the served
tokens.
"""
import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))
# the LM cells, kept out of BENCHMARK.json until a comparison proves them
sys.path.insert(0, str(ROOT / "bench" / "tests"))


def say(**kw):
    print(json.dumps(kw), flush=True)


def bits(a):
    import numpy as np
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt", type=int, default=300)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--fences", nargs="*", default=[],
                    help="other fences of the reference's decode matmuls to "
                         "try first: '', 'in', 'out', 'in+out'")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    import cellfiles
    from harness import cells, runner, seeds
    cell = cellfiles.find(args.workload)
    cfg, mix = runner.effective(cell, args.rehearse)
    if not args.rehearse:
        from repro.launch.serve import setup_compile_cache
        setup_compile_cache()
    drv, ref = cells.driver(cell), cells.reference(cell)
    sess = drv.Session(cfg, mix, args.seed)
    eng = sess.sched.engine
    P = min(args.prompt, mix["prompt_pad"])
    T = min(args.steps, mix["max_len"] - P - 1)
    toks = seeds.rng(args.seed, 11).integers(
        0, cfg["vocab_size"], size=(P,)).astype(np.int32)
    pre = eng.prefill(toks)
    state = eng.init_state()
    state, view = eng.insert(pre, state, max_new_tokens=T + 1)
    inserted = jax.tree_util.tree_map(jnp.copy, state.cache)
    for _ in range(T - 1):
        state, _ = eng.generate(state)
    served = np.asarray(view.tokens, np.int32)
    S = P + T - 1
    kp = bits(state.cache["k"][:, view.slot, :S])
    vp = bits(state.cache["v"][:, view.slot, :S])
    plan0 = jax.tree_util.tree_map(lambda a: a[0], eng.params["layers"])
    del state, pre

    # the same decoded rows again through the model's own decode_step,
    # jitted by itself, from the cache as insert left it: where it agrees
    # with the engine and the reference does not, the reference would have
    # to follow the model's decode program; where it differs too, the
    # engine's wrapper around it sets the rounding
    from repro.models import lm
    step = jax.jit(lambda p, c, t, i: lm.decode_step(p, sess.mcfg, c, t, i)[1],
                   donate_argnums=(1,))
    cache = inserted
    for j in range(T - 1):
        tok = np.zeros((mix["slots"], 1), np.int32)
        idx = np.zeros((mix["slots"],), np.int32)
        tok[view.slot, 0], idx[view.slot] = served[j], P + j
        cache = step(eng.params, cache, jnp.asarray(tok), jnp.asarray(idx))
    for name, eng_rows in (("k", kp), ("v", vp)):
        own = bits(cache[name][:, view.slot, P:S])
        rows = np.any(own != eng_rows[:, P:S], axis=(2, 3))
        say(model_decode_step_vs_engine=name,
            layers_differing=np.flatnonzero(rows.any(axis=1)).tolist()[:6],
            layer0_rows=np.flatnonzero(rows[0]).tolist())
    del cache, inserted
    sess.free()

    dm = ref._dims(cfg, mix)
    dims = tuple(sorted(dm.items()))
    ks = jax.random.split(seeds.jax_key(args.seed, seeds.WEIGHTS), 8)
    w0 = ref._layer_weights(jax.random.split(ks[1], dm["layers"])[0], dims)
    for n, leaf in (("q", "wq_dh"), ("k", "wk_dh"), ("o", "wo_hd")):
        pl = plan0["attn"][leaf]
        say(weight=n, code_mismatches=int(np.sum(
            np.asarray(pl.values) != np.asarray(w0[n][0]))),
            scale_mismatches=int(np.sum(bits(pl.scale) != bits(w0[n][1]))))

    # layer 0's decoded rows three ways: the engine's cache, the program's
    # own functions jitted apart at the decode batch's shape, and the
    # reference. Where the last two agree and the engine differs, the
    # rounding comes from how the engine's decode program is compiled.
    from repro.models.layers import apply_rope, proj, rms_norm
    rows, m = dm["rows"], min(T - 1, dm["rows"])
    ids = np.zeros((rows,), np.int32)
    ids[:m] = served[:m]
    pos = np.full((rows,), P + m - 1, np.int32)
    pos[:m] = P + np.arange(m)
    kv_dt = getattr(jnp, dm["kv_dtype"])

    def program(x, pos, ln, a):
        h = rms_norm(x, ln, dm["eps"])
        k = (proj(h, a["wk_dh"]) + a["bk_bh"]).reshape(rows, 1, dm["kv"],
                                                        dm["hd"])
        v = proj(h, a["wv_dh"]) + a["bv_bh"]
        return (apply_rope(k, pos[:, None], dm["theta"])[:, 0].astype(kv_dt),
                v.reshape(rows, dm["kv"], dm["hd"]).astype(kv_dt))

    def differ(a, b):
        a, b = bits(a)[:m], bits(b)[:m]
        return np.flatnonzero(np.any(a != b, axis=(1, 2))).tolist()

    with jax.default_matmul_precision(cfg["matmul_precision"]):
        table = ref._embedding(ks[0], dims)
        x = table[jnp.asarray(ids)][:, None]
        def reference(x, pos, w):
            _, k, v = ref._qkv(x, w, pos[:, None], dm)
            return k[:, 0].astype(kv_dt), v[:, 0].astype(kv_dt)

        k_ref, v_ref = jax.jit(reference)(x, jnp.asarray(pos), w0)
        k_prog, v_prog = jax.jit(program)(x, jnp.asarray(pos),
                                          plan0["ln1_d"], plan0["attn"])
    k_eng, v_eng = kp[0, P:P + m], vp[0, P:P + m]
    say(layer0_decoded_rows=m,
        engine_vs_reference=dict(k=differ(k_eng, k_ref), v=differ(v_eng, v_ref)),
        engine_vs_program_functions=dict(k=differ(k_eng, k_prog),
                                         v=differ(v_eng, v_prog)),
        program_functions_vs_reference=dict(k=differ(k_prog, k_ref),
                                            v=differ(v_prog, v_ref)))

    found = {}

    def on_layer(i, j, kc, vc):
        bk = np.any(bits(kc[:S]) != kp[i], axis=(1, 2))
        bv = np.any(bits(vc[:S]) != vp[i], axis=(1, 2))
        row = dict(layer=i, k_prompt=int(bk[:P].sum()),
                   k_decoded=int(bk[P:].sum()), v_prompt=int(bv[:P].sum()),
                   v_decoded=int(bv[P:].sum()))
        if (bk.any() or bv.any()) and "first" not in found:
            found["first"] = i
            row["first_rows"] = np.flatnonzero(bk | bv)[:8].tolist()
        if i < 3 or ("first" in found and i <= found["first"] + 2):
            say(**row)

    sample = [{"prompt": toks, "tokens": served}]
    for fence in args.fences:
        fence = tuple(f for f in fence.split("+") if f)
        if fence == ref.DECODE_FENCE:
            continue
        seen = {}

        def first(i, j, kc, vc, seen=seen):
            if "first" not in seen and (
                    np.any(bits(kc[:S]) != kp[i]) or
                    np.any(bits(vc[:S]) != vp[i])):
                seen["first"] = i

        ref.forward(cfg, mix, args.seed, sample, jnp.float32, on_layer=first,
                    fence=fence)
        say(fence=list(fence), first_layer_differing=seen.get("first"))
    lg = ref.forward(cfg, mix, args.seed, sample, jnp.float32,
                     on_layer=on_layer)[0]
    gap = lg.max(axis=-1) - np.take_along_axis(lg, served[:, None], 1)[:, 0]
    say(first_layer_differing=found.get("first"), prompt=P, decoded=T - 1,
        token_gap=float(gap.max()), first_token_gap=float(gap[0]),
        token_miss=int(np.sum(served != np.argmax(lg, axis=-1))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
