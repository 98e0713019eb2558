"""Static-analysis pass + runtime sanitizers: per-rule fixtures with
exact rule ids and line numbers, inline suppression, registry plumbing,
a self-run over the real tree (must stay at zero findings — the CI
gate), hot-set assertions on the call graph, and the sanitizer layer
(transfer guard trips on a deliberately host-syncing decode loop but
not on the real scheduler; compile-count sentinel)."""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (available_checkers, get_checker, lint_paths,
                            lint_source)
from repro.analysis.lint import build_project
from repro.analysis.sanitize import (CompileCountError, CompileCounter,
                                     Sanitizer)
from repro.configs.base import get_config
from repro.models.lm import init_lm
from repro.serving import ContinuousScheduler, poisson_trace

REPO = __file__.rsplit("/tests/", 1)[0]


def _lint(src, **kw):
    return lint_source(textwrap.dedent(src), **kw)


def _hits(findings):
    return [(f.rule, f.line) for f in findings]


# ---------------------------------------------------------------------------
# per-rule fixtures: exact rule id and line number
# ---------------------------------------------------------------------------
def test_rpr101_float_on_traced():
    f = _lint("""\
    import jax.numpy as jnp

    def hot(x):
        y = jnp.sum(x)
        return float(y)
    """)
    assert _hits(f) == [("RPR101", 5)]


def test_rpr101_item_and_tolist():
    f = _lint("""\
    import jax.numpy as jnp

    def hot(x):
        y = jnp.argmax(x)
        a = y.item()
        b = (y + 1).tolist()
        return a, b
    """)
    assert _hits(f) == [("RPR101", 5), ("RPR101", 6)]


def test_rpr101_np_asarray_on_traced():
    f = _lint("""\
    import jax.numpy as jnp
    import numpy as np

    def hot(x):
        y = jnp.exp(x)
        return np.asarray(y)
    """)
    assert _hits(f) == [("RPR101", 6)]


def test_rpr101_taint_through_method_chain():
    # jnp.argmax(x).astype(...) keeps the taint through the method call
    f = _lint("""\
    import jax.numpy as jnp

    def hot(x):
        tok = jnp.argmax(x, -1).astype(jnp.int32)
        return int(tok)
    """)
    assert _hits(f) == [("RPR101", 5)]


def test_rpr102_truthiness_of_traced():
    f = _lint("""\
    import jax.numpy as jnp

    def hot(x):
        y = jnp.max(x)
        if y > 0:
            return 1
        return 0
    """)
    assert _hits(f) == [("RPR102", 5)]


def test_rpr201_fresh_jit_per_call():
    f = _lint("""\
    import jax

    def step(f, x):
        return jax.jit(f)(x)
    """, assume_hot=False)
    assert _hits(f) == [("RPR201", 4)]


def test_rpr202_branch_inside_jit_target():
    f = _lint("""\
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        y = jnp.sum(x)
        if y > 0:
            return y
        return -y
    """, assume_hot=False)
    assert _hits(f) == [("RPR202", 7)]


def test_rpr203_set_iteration():
    f = _lint("""\
    def build(keys):
        s = set(keys)
        return [k for k in s]
    """, assume_hot=False)
    assert any(r == "RPR203" and ln == 3 for r, ln in _hits(f))


def test_rpr301_unregistered_array_dataclass():
    f = _lint("""\
    import dataclasses
    import jax

    @dataclasses.dataclass
    class State:
        x: jax.Array
        step: int
    """, assume_hot=False)
    assert _hits(f) == [("RPR301", 5)]


def test_rpr301_registered_is_clean():
    f = _lint("""\
    import dataclasses
    import jax
    from jax.tree_util import register_pytree_node_class

    @register_pytree_node_class
    @dataclasses.dataclass
    class State:
        x: jax.Array
    """, assume_hot=False)
    assert f == []


def test_rpr401_blockspec_minor_dim():
    f = _lint("""\
    from jax.experimental import pallas as pl

    TILE = 64

    def kernel(x):
        a = pl.BlockSpec((8, 100), lambda i: (i, 0))
        b = pl.BlockSpec((8, TILE), lambda i: (i, 0))
        c = pl.BlockSpec((8, 128), lambda i: (i, 0))
        return a, b, c
    """, assume_hot=False)
    assert _hits(f) == [("RPR401", 6), ("RPR401", 7)]


def test_rpr402_interpret_default_true():
    f = _lint("""\
    def run_kernel(x, interpret=True):
        return x
    """, assume_hot=False)
    assert _hits(f) == [("RPR402", 1)]


def test_rpr403_pallas_call_without_name():
    f = _lint("""\
    from jax.experimental import pallas as pl

    def run(x):
        a = pl.pallas_call(kernel, out_shape=x)(x)
        return pl.pallas_call(kernel, out_shape=x, name="k")(a)
    """, assume_hot=False)
    assert _hits(f) == [("RPR403", 4)]


# ---------------------------------------------------------------------------
# negatives: the sanctioned patterns stay quiet
# ---------------------------------------------------------------------------
def test_device_get_is_the_sanctioned_sync():
    f = _lint("""\
    import jax
    import jax.numpy as jnp

    def hot(x):
        y = jnp.sum(x)
        return float(jax.device_get(y))
    """)
    assert f == []


def test_static_attrs_are_host_values():
    f = _lint("""\
    import jax.numpy as jnp

    def hot(x):
        y = jnp.exp(x)
        if y.shape[0] > 4 and y.dtype == jnp.float32:
            return int(y.ndim)
        return 0
    """)
    assert f == []


def test_identity_tests_are_host_bools():
    f = _lint("""\
    import jax.numpy as jnp

    def hot(x, bias):
        y = jnp.exp(x)
        if bias is not None:
            y = y + bias
        return y
    """)
    assert f == []


# ---------------------------------------------------------------------------
# suppression + registry + hot-set plumbing
# ---------------------------------------------------------------------------
def test_inline_suppression_same_and_previous_line():
    f = _lint("""\
    import jax.numpy as jnp

    def hot(x):
        y = jnp.sum(x)
        a = float(y)  # repro-lint: disable=RPR101
        # repro-lint: disable=all
        b = float(y)
        c = float(y)
        return a, b, c
    """)
    assert _hits(f) == [("RPR101", 8)]


def test_select_and_ignore():
    src = """\
    import jax.numpy as jnp

    def hot(x, interpret=True):
        return float(jnp.sum(x))
    """
    assert {r for r, _ in _hits(_lint(src))} == {"RPR101", "RPR402"}
    assert _hits(_lint(src, select=["RPR402"])) == [("RPR402", 3)]
    assert _hits(_lint(src, ignore=["RPR402"])) == [("RPR101", 4)]


def test_checker_registry():
    names = available_checkers()
    assert set(names) == {"host-sync", "recompile", "pytree",
                          "pallas-tile"}
    assert get_checker("host-sync").rules == ("RPR101", "RPR102")
    with pytest.raises(ValueError, match="unknown checker"):
        get_checker("nope")


def test_hot_set_covers_scheduler_and_benchmarks():
    project = build_project([f"{REPO}/src", f"{REPO}/benchmarks"],
                            root=REPO)
    hot = project.hot
    assert "repro.serving.scheduler.ContinuousScheduler.run" in hot
    assert "repro.models.lm.decode_step" in hot
    # reached through a local _Executor instance inside cnn_forward
    assert "repro.benchmarks_impl.table2._acc" in hot
    # training loop is not on a decode/serve hot path root
    assert not project.is_hot("repro.launch.train.main")


def test_self_run_is_clean():
    """The CI gate: the analyzer over the real tree reports nothing."""
    findings = lint_paths([f"{REPO}/src", f"{REPO}/benchmarks"],
                          root=REPO)
    assert findings == [], "\n".join(x.render() for x in findings)


# ---------------------------------------------------------------------------
# runtime sanitizers
# ---------------------------------------------------------------------------
def test_transfer_guard_trips_on_host_syncing_decode_loop():
    """A decode loop that feeds raw numpy into the step function does an
    implicit host->device transfer every iteration — exactly what the
    guard bans in the steady state."""
    san = Sanitizer()

    @jax.jit
    def bad_step(tok):
        return tok + 1

    tok = np.zeros((4,), np.int32)
    bad_step(jnp.asarray(tok))  # warm the cache outside the guard
    with pytest.raises(Exception, match="[Dd]isallowed"):
        with san.decode_guard():
            bad_step(tok)  # implicit transfer of the numpy operand


def test_explicit_device_put_is_legal_under_guard():
    san = Sanitizer()

    @jax.jit
    def step(tok):
        return tok + 1

    step(jnp.zeros((4,), jnp.int32))
    with san.decode_guard():
        out = step(jax.device_put(np.zeros((4,), np.int32)))
    assert int(jax.device_get(out[0])) == 1


def test_sanitized_scheduler_run_is_transfer_clean():
    """The real scheduler under an armed sanitizer: zero disallowed
    transfers and exactly one compile per step function."""
    cfg = get_config("qwen2.5-3b").reduced(num_layers=2, d_model=64,
                                           vocab=128)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    san = Sanitizer()
    sched = ContinuousScheduler(params, cfg, num_slots=2, prompt_pad=8,
                                max_len=16, sanitizer=san)
    reqs = poisson_trace(n=4, rate=0.0, prompt_lens=[2, 5],
                         gen_lens=[2, 4], vocab=cfg.vocab_size, seed=3)
    with san.compile_counter(
            names=("prefill", "insert", "decode")) as counter:
        sched.warmup()
        res = sched.run(reqs)
    assert len(res.completions) == len(reqs)
    counter.expect(prefill=1, insert=1, decode=1)


def test_compile_counter_counts_and_expects():
    with CompileCounter(names=("cc_fixture_fn",)) as c:
        @jax.jit
        def cc_fixture_fn(x):
            return x * 2

        cc_fixture_fn(jnp.ones(3))
        cc_fixture_fn(jnp.ones(3))  # cached: no recompile
        assert c.count("cc_fixture_fn") == 1
    c.expect(cc_fixture_fn=1)
    with pytest.raises(CompileCountError):
        c.expect(cc_fixture_fn=2)


def test_compile_counter_catches_retrace():
    with CompileCounter(names=("cc_retrace_fn",)) as c:
        @jax.jit
        def cc_retrace_fn(x):
            return x + 1

        cc_retrace_fn(jnp.ones(3))
        cc_retrace_fn(jnp.ones(5))  # new shape -> retrace
    with pytest.raises(CompileCountError, match="cc_retrace_fn"):
        c.expect(cc_retrace_fn=1)
