"""Compile rehearsal: the serving kernels, compiled for a described TPU v5e.

No chip is needed. The TPU compiler that ships with jaxlib compiles each
Pallas kernel with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology, at the published DVGO table width (grid 160, so
20^3 MVoxels of 729 halo rows, 12 channels) and at the ragged RIT's
static block counts for the preview cell's streams (two slots of 192x192
frames, 192 samples per ray, a 16384-ray pool bucket and prime chunk,
512 columns per block), and the fused decoder at the repository's MLP(64)
and at DVGO's fine stage (13 channels, rgbnet 39 -> 128 -> 128 -> 3, over
the tick's reference stream). Each test checks that the Mosaic kernel is in the
compiled program and that its arguments plus temporaries fit one v5e's
HBM; the ragged sweeps also check their blocks against the kernels'
scoped VMEM limit and their scalar-prefetched keys against the core's
scalar memory. A kernel the chip's compiler refuses, or an operand layout
that pads past the chip's memory, fails here.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis import pallas_pass
from repro.core import streaming
from repro.kernels import fused_nerf_mlp, gather_trilerp, streaming_pipeline

GRID, CHANNELS = 160, 12
NUM_MV = (GRID // 8) ** 3   # 8^3-vertex MVoxels
HALO_ROWS = 9 ** 3
BLOCK = 512                 # default stream_capacity: columns per RIT block
SLOTS, RES, SAMPLES, POOL = 2, 192, 192, 16384
# the tick's merged stream: pooled holes and the next references
TICK_SAMPLES = SLOTS * (POOL + RES * RES) * SAMPLES      # 20.4M
PRIME_SAMPLES = POOL * SAMPLES                           # 3.1M a chunk
PAGES = 2                   # a mixed-scene tick's stacked resident set
HIDDEN, DIRENC = 64, 9
FINE_CHANNELS = 13          # DVGO's fine stage: raw density and 12 k0
HBM_BYTES = 15.75e9         # one v5e as the compiler counts it

# kernel case -> (wrapper, samples in its stream, table pages): a prime
# chunk, the tick, and a mixed-scene tick
RAGGED = {
    "gather_trilerp_mvoxels_segmented": (
        "gather_trilerp_mvoxels_segmented", PRIME_SAMPLES, 1),
    "fused_gather_dual": ("fused_gather_dual", TICK_SAMPLES, 1),
    "fused_gather_dual_stacked": ("fused_gather_dual", TICK_SAMPLES, PAGES),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _wrapper(name):
    return getattr(gather_trilerp, name, None) or getattr(
        streaming_pipeline, name)


def _ragged_args(samples, pages, sds):
    f32, i32 = jnp.float32, jnp.int32
    keys = pages * NUM_MV
    n_blocks = streaming.rit_num_blocks(samples, keys, BLOCK)
    return (sds((keys, HALO_ROWS, CHANNELS), f32), sds((n_blocks,), i32),
            sds((1,), i32), sds((n_blocks, 8, BLOCK), i32),
            sds((n_blocks, 8, BLOCK), f32))


def _kernel_call(case, sds):
    """(function, argument shapes) for one kernel at the serving width."""
    f32 = jnp.float32
    if case in RAGGED:
        name, samples, pages = RAGGED[case]
        fn = _wrapper(name)
        return (lambda *a: fn(*a, interpret=False)), _ragged_args(
            samples, pages, sds)
    if case == "fused_nerf_mlp_rgbnet":
        return _rgbnet_call(sds)
    assert case == "fused_nerf_mlp"
    s, h, d = BLOCK * 64, HIDDEN, DIRENC
    return (lambda *a: fused_nerf_mlp.fused_nerf_mlp(
        *a, block=BLOCK, interpret=False)), (
        sds((s, CHANNELS), f32), sds((s, d), f32), sds((CHANNELS, h), f32),
        sds((1, h), f32), sds((h, h), f32), sds((1, h), f32),
        sds((h, 1), f32), sds((h + d, 3), f32), sds((1, 3), f32))


def _rgbnet_call(sds):
    """DVGO's fine-stage decoder at its published widths (13 channels:
    raw density and 12 k0; rgbnet 39 -> 128 -> 128 -> 3, 4 view-encoding
    frequencies) over the tick's reference stream, through the ops
    wrapper the model calls (channel-major operands, the split first
    layer)."""
    from repro.kernels import ops
    from repro.nerf import mlp

    dcfg = mlp.DecoderCfg(mode="rgbnet", in_channels=FINE_CHANNELS,
                          hidden=128, viewbase_pe=4,
                          voxel_size=2.0 / (GRID - 1))
    widths = (dcfg.rgbnet_in, 128, 128, 3)
    params = {}
    for i, (a, b) in enumerate(zip(widths[:-1], widths[1:]), start=1):
        params[f"w{i}"] = sds((a, b), jnp.float32)
        params[f"b{i}"] = sds((b,), jnp.float32)
    s = SLOTS * RES * RES * SAMPLES
    return (lambda f, d, p: ops.dvgo_rgbnet(f, d, p, dcfg, tick=True,
                                            interpret=False),
            (sds((s, FINE_CHANNELS), jnp.float32),
             sds((s, 3), jnp.float32), params))


@pytest.mark.parametrize("case", [*RAGGED, "fused_nerf_mlp",
                                  "fused_nerf_mlp_rgbnet"])
def test_kernel_compiles_for_v5e_and_fits(case, one_chip,
                                          no_persistent_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_call(case, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used <= HBM_BYTES, (
        f"{case}: arguments + temporaries {used / 1e9:.2f} GB exceed one "
        f"v5e's {HBM_BYTES / 1e9} GB")


@pytest.mark.parametrize("case", list(RAGGED))
def test_ragged_sweep_fits_vmem_and_smem(case):
    # the blocks the grid pipeline double-buffers stay inside the scoped
    # VMEM the kernel asks for, and the scalar-prefetched block keys
    # inside one core's scalar memory
    name, samples, pages = RAGGED[case]
    args = _ragged_args(samples, pages, jax.ShapeDtypeStruct)
    (rec,) = pallas_pass.record_launches(_wrapper(name), *args,
                                         interpret=True)
    assert rec.grid == (args[1].shape[0],)
    assert rec.vmem_bytes <= gather_trilerp.COMPILER_PARAMS.vmem_limit_bytes
    smem = 4 * (args[1].shape[0] + args[2].shape[0])
    assert smem <= gather_trilerp.SMEM_BYTES


def test_ragged_sweep_refuses_keys_past_smem():
    # past one core's scalar memory the kernel refuses at trace time,
    # rather than chunking the sweep in silence
    n_blocks = gather_trilerp.SMEM_BYTES // 4
    args = (jnp.zeros((1, HALO_ROWS, CHANNELS)),
            jnp.zeros((n_blocks,), jnp.int32), jnp.zeros((1,), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 8, BLOCK), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 8, BLOCK), jnp.float32))
    with pytest.raises(ValueError, match="scalar memory"):
        jax.eval_shape(lambda *a: gather_trilerp.gather_blocks(
            *a, interpret=False), *args)


def _assert_keeps_its_name(case, scope, one_chip):
    # a profile names a kernel's event after the instruction, which takes
    # the name of the jitted function around the pallas_call; the roofline
    # metrics read it. A scope opened inside that function renames it.
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_call(case, sds)
    name = RAGGED[case][0] if case in RAGGED else "fused_nerf_mlp"

    def scoped(*a):
        with jax.named_scope(scope):
            return fn(*a)

    text = jax.jit(scoped).lower(*args).compile().as_text()
    assert re.search(rf"^\s*%{name}\.\d+ = .* custom-call\(", text, re.M)


@pytest.mark.parametrize("case", ["gather_trilerp_mvoxels_segmented",
                                  "fused_gather_dual"])
def test_kernel_keeps_its_name_under_the_gather_scope(case, one_chip,
                                                      no_persistent_cache):
    _assert_keeps_its_name(case, "gather", one_chip)


def test_mlp_kernel_keeps_its_name_under_the_decode_scope(
        one_chip, no_persistent_cache):
    # NerfModel.decode_features opens ``decode`` around ops.nerf_mlp
    _assert_keeps_its_name("fused_nerf_mlp", "decode", one_chip)


def test_rgbnet_kernel_keeps_its_name_under_the_decode_scope(
        one_chip, no_persistent_cache):
    # ... and around ops.dvgo_rgbnet, whose fused tick's call the
    # ``fused_nerf_mlp_roofline`` reader reads
    _assert_keeps_its_name("fused_nerf_mlp_rgbnet", "decode", one_chip)
