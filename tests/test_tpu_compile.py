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


# The CNN cells' extreme calls as (M, Kp, Np), w4a4 with a bias: VGG's
# s0c1 (the most rows, 2048-row tiles), ResNet's stage-3 convs (the
# deepest whole-K step at the widest N tile), VGG's fc1 (the deepest K,
# split into K steps). Each takes the tiles ``kernel_tiles`` picks.
CNN_EXTREMES = [(3211264, 1024, 64), (16384, 4608, 512), (64, 25088, 4096)]


@pytest.mark.parametrize("m,k,n", CNN_EXTREMES,
                         ids=["vgg-s0c1", "resnet-s3", "vgg-fc1"])
def test_fused_pim_matmul_compiles_at_cnn_extremes(one_chip, m, k, n):
    _compile(functools.partial(pim_matmul_fused_pallas, interpret=False),
             one_chip, ((1, m, k), jnp.int8), ((1, k, n), jnp.int8),
             ((m, 1), jnp.float32), ((1, n), jnp.float32),
             ((1, n), jnp.float32))


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


CNN_CELLS = ["resnet18-cifar100.batch1024", "vgg16-imagenette.batch64"]


def _cnn_forward(one_chip, cell_name, rehearse):
    """A CNN benchmark cell's forward, at its rehearsal sizes or its own,
    compiled for the described chip: (compiled, layer specs, batch)."""
    import dataclasses
    import pathlib
    import sys
    bench = pathlib.Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    from harness import cells, runner

    from repro.models.cnn import cnn_forward, init_cnn, plan_cnn_weights

    cell = cells.find(cell_name)
    cfg, mix = runner.effective(cell, rehearse)
    drv = cells.driver(cell)
    layers = drv.layer_specs(cfg)
    pcfg = dataclasses.replace(drv.pim_config(cfg), interpret=False)
    place = lambda t: jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), t)
    params = jax.eval_shape(lambda: init_cnn(layers, jax.random.PRNGKey(0)))
    plans = jax.eval_shape(lambda p: plan_cnn_weights(p, layers, pcfg),
                           params)
    hw = cfg["image_size"]
    x = jax.ShapeDtypeStruct((mix["batch"], hw, hw, 3), jnp.float32)
    compiled = jax.jit(lambda p, pl, x: cnn_forward(
        p, layers, x, pim=pcfg, plans=pl)).lower(
            place(params), place(plans), place(x)).compile()
    return compiled, layers, mix["batch"]


@functools.lru_cache(maxsize=None)
def _cnn_forward_text(one_chip, cell_name):
    """The cell's forward at its rehearsal sizes, compiled for the
    described chip: (optimized HLO text, layer specs, batch)."""
    compiled, layers, b = _cnn_forward(one_chip, cell_name, True)
    return compiled.as_text(), layers, b


@pytest.mark.parametrize("cell", CNN_CELLS)
def test_cnn_forward_kernels_sit_under_kernel_scopes(one_chip, cell):
    """A CNN cell's forward at its rehearsal sizes: every Mosaic
    kernel's instruction carries its layer's ``kernel`` scope in the
    compiled text, which is how a trace reduction finds the kernel's own
    device events (the events carry no metadata)."""
    text, layers, _ = _cnn_forward_text(one_chip, cell)
    from harness import scopes

    from repro.core import pim

    names = scopes.op_names(text)
    kernels = [scopes.instruction(line) for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == len(layers)
    known = pim.SCOPES + tuple(spec.name for spec in layers)
    assert [scopes.innermost(names.get(k), known) for k in kernels] == \
        [pim.SCOPE_KERNEL] * len(layers)


@pytest.mark.parametrize("cell", CNN_CELLS)
def test_cnn_forward_writes_no_float_patch_buffer(one_chip, cell):
    """No buffer of the compiled forward (an instruction of its entry
    computation) is an f32 or s32 array shaped like a 3x3 conv's patch
    matrix, (B, oh, ow, K') in any order of (B, oh, ow) with K' at least
    its K, or (M, K): the exact route writes the patches as int8 planes
    only."""
    import re

    from repro.core.workloads import ConvSpec

    text, layers, b = _cnn_forward_text(one_chip, cell)
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    buffers = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) [a-z][\w-]*\(", line)
        if m:
            buffers += [(m.group(1), tuple(int(d) for d in dims.split(",")))
                        for dims in re.findall(r"\b(?:f32|s32)\[([\d,]+)\]",
                                               m.group(2))]
    assert buffers
    for spec in layers:
        if not isinstance(spec, ConvSpec) or spec.kh * spec.kw == 1:
            continue
        k = spec.kh * spec.kw * spec.in_c
        lead = (b, spec.out_h, spec.out_w)
        patch = [(name, shape) for name, shape in buffers
                 if (len(shape) == 4 and sorted(shape[:3]) == sorted(lead)
                     and shape[3] >= k)
                 or shape == (b * spec.out_h * spec.out_w, k)]
        assert patch == [], (spec.name, patch)


def test_vgg_cell_forward_compiles_at_full_size(one_chip):
    """The VGG16 cell's forward at its own sizes (batch 64, 224 px, every
    published width; fc1 flattens 25,088 features): Mosaic accepts every
    kernel, and its temporaries and arguments leave the 16 GB chip room."""
    compiled, layers, _ = _cnn_forward(one_chip, "vgg16-imagenette.batch64",
                                       False)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == len(layers)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 12e9
