"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at a real serving shape of
qwen2.5-3b (d_model 2048, d_ff 11008) and compiles it with the TPU
compiler against a ``v5e:2x2`` topology that is described, not attached.
That is what Mosaic would refuse on the chip — unsupported operand
types, unaligned slices, block shapes off the (8, 128) tiling, VMEM
overflow — caught here at no chip time. Interpret-mode tests elsewhere
cannot see any of it.

The topology is described inside a module-scoped fixture (never at
import): only the worker that runs this file loads the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.analog_readout.analog_readout import (
    analog_fullscale_pallas, analog_readout_pallas)
from repro.kernels.pim_matmul.pim_matmul import (pim_matmul_fused_pallas,
                                                 pim_matmul_pallas)

K, N = 2048, 11008          # qwen2.5-3b d_model x d_ff (the MLP up/gate)
DECODE_M, PREFILL_M = 8, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            # the TPU compiler would otherwise write its logs under /tmp
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "skip"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Lower ``fn`` on shape structs placed on the described chip and
    compile it; returns the optimized HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _plane_shapes(m, planes):
    return [((planes, m, K), jnp.int8), ((planes, K, N), jnp.int8)]


def _scale_shapes(m):
    return [((m, 1), jnp.float32), ((1, N), jnp.float32)]


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M],
                         ids=["decode", "prefill"])
@pytest.mark.parametrize("bits", [4, 8], ids=["w4a4", "w8a8"])
def test_fused_pim_matmul_compiles(one_chip, m, bits):
    planes = bits // 4
    _compile(functools.partial(pim_matmul_fused_pallas, interpret=False),
             one_chip, *_plane_shapes(m, planes), *_scale_shapes(m))


def test_fused_pim_matmul_rowsum_compiles(one_chip):
    """The ABFT route: the fused kernel also emits accumulator row-sums."""
    _compile(functools.partial(pim_matmul_fused_pallas, interpret=False,
                               want_rowsum=True),
             one_chip, *_plane_shapes(DECODE_M, 1), *_scale_shapes(DECODE_M))


def test_raw_pim_matmul_compiles(one_chip):
    """The raw int32 kernel (mesh row split, kernel check)."""
    _compile(functools.partial(pim_matmul_pallas, interpret=False),
             one_chip, *_plane_shapes(DECODE_M, 1))


def test_analog_fullscale_compiles(one_chip):
    _compile(functools.partial(analog_fullscale_pallas, chunk=8,
                               interpret=False),
             one_chip, *_plane_shapes(DECODE_M, 1))


def test_analog_readout_compiles(one_chip):
    _compile(functools.partial(analog_readout_pallas, chunk=8,
                               interpret=False),
             one_chip, *_plane_shapes(DECODE_M, 1), *_scale_shapes(DECODE_M),
             ((), jnp.float32))


def test_data_only_mesh_matmul_compiles(topo):
    """A (4, 1) ("data", "model") mesh splits nothing, but the plan must
    still be stamped: Mosaic kernels cannot be partitioned automatically,
    so a Pallas matmul on a data-sharded batch has to run in shard_map."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import engine
    from repro.core.pim import PimConfig

    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    cfg = PimConfig(substrate="exact-pallas", interpret=False)
    plan = jax.eval_shape(lambda: engine.program(
        jnp.zeros((K, N), jnp.float32), cfg, mesh=mesh, spec="row"))
    replicated = NamedSharding(mesh, P())
    plan = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=replicated), plan)
    x = jax.ShapeDtypeStruct((DECODE_M, K), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", None)))
    text = jax.jit(lambda a, p: engine.matmul(a, p)).lower(
        x, plan).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
