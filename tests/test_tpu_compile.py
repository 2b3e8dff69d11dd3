"""Compile rehearsal: the serving kernels, compiled for a described TPU v5e.

No chip is needed. The TPU compiler that ships with jaxlib compiles each
Pallas kernel with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology, at the published DVGO table width (grid 160, so
20^3 MVoxels of 729 halo rows, 12 channels) and at the RIT capacities and
segment count that ``chip_smoke.py`` serves with. Each test checks that
the Mosaic kernel is in the compiled program and that its arguments plus
temporaries fit one v5e's HBM. A kernel the chip's compiler refuses, or
an operand layout that pads past the chip's memory, fails here.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_nerf_mlp, gather_trilerp, streaming_pipeline

GRID, CHANNELS = 160, 12
NUM_MV = (GRID // 8) ** 3   # 8^3-vertex MVoxels
HALO_ROWS = 9 ** 3
NUM_SEG = 2                 # chip_smoke.py's slots
CAP_H, CAP_R = 512, 1024    # default stream_capacity, x2 for references
HIDDEN, DIRENC, BLOCK = 64, 9, 512
HBM_BYTES = 15.75e9         # one v5e as the compiler counts it


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


def _kernel_call(name, sds):
    """(function, argument shapes) for one kernel at the serving width."""
    f32, i32 = jnp.float32, jnp.int32
    table = sds((NUM_MV, HALO_ROWS, CHANNELS), f32)
    tables = sds((NUM_SEG, NUM_MV, HALO_ROWS, CHANNELS), f32)
    hole = (sds((NUM_SEG * NUM_MV, 8, CAP_H), i32),
            sds((NUM_SEG * NUM_MV, 8, CAP_H), f32))
    ref = (sds((NUM_SEG * NUM_MV, 8, CAP_R), i32),
           sds((NUM_SEG * NUM_MV, 8, CAP_R), f32))
    kw = dict(num_seg=NUM_SEG, interpret=False)
    if name == "gather_trilerp_mvoxels_segmented":
        return (lambda *a: gather_trilerp.gather_trilerp_mvoxels_segmented(
            *a, **kw)), (table, *hole)
    if name == "gather_trilerp_mvoxels_per_seg":
        return (lambda *a: gather_trilerp.gather_trilerp_mvoxels_per_seg(
            *a, **kw)), (tables, *hole)
    if name == "fused_gather_dual":
        return (lambda *a: streaming_pipeline.fused_gather_dual(*a, **kw)), (
            table, *hole, *ref)
    if name == "fused_gather_dual_per_seg":
        return (lambda *a: streaming_pipeline.fused_gather_dual_per_seg(
            *a, **kw)), (tables, *hole, *ref)
    assert name == "fused_nerf_mlp"
    s, h, d = BLOCK * 64, HIDDEN, DIRENC
    return (lambda *a: fused_nerf_mlp.fused_nerf_mlp(
        *a, block=BLOCK, interpret=False)), (
        sds((s, CHANNELS), f32), sds((s, d), f32), sds((CHANNELS, h), f32),
        sds((1, h), f32), sds((h, h), f32), sds((1, h), f32),
        sds((h, 1), f32), sds((h + d, 3), f32), sds((1, 3), f32))


@pytest.mark.parametrize("name", [
    "gather_trilerp_mvoxels_segmented",
    "gather_trilerp_mvoxels_per_seg",
    "fused_gather_dual",
    "fused_gather_dual_per_seg",
    "fused_nerf_mlp",
])
def test_kernel_compiles_for_v5e_and_fits(name, one_chip,
                                          no_persistent_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_call(name, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used <= HBM_BYTES, (
        f"{name}: arguments + temporaries {used / 1e9:.2f} GB exceed one "
        f"v5e's {HBM_BYTES / 1e9} GB")


def _assert_keeps_its_name(name, scope, one_chip):
    # a profile names a kernel's event after the instruction, which takes
    # the name of the jitted function around the pallas_call; the roofline
    # metrics read it. A scope opened inside that function renames it.
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_call(name, sds)

    def scoped(*a):
        with jax.named_scope(scope):
            return fn(*a)

    text = jax.jit(scoped).lower(*args).compile().as_text()
    assert re.search(rf"^\s*%{name}\.\d+ = .* custom-call\(", text, re.M)


@pytest.mark.parametrize("name", [
    "gather_trilerp_mvoxels_segmented",
    "gather_trilerp_mvoxels_per_seg",
    "fused_gather_dual",
    "fused_gather_dual_per_seg",
])
def test_kernel_keeps_its_name_under_the_gather_scope(name, one_chip,
                                                      no_persistent_cache):
    _assert_keeps_its_name(name, "gather", one_chip)


def test_mlp_kernel_keeps_its_name_under_the_decode_scope(
        one_chip, no_persistent_cache):
    # NerfModel.decode_features opens ``decode`` around ops.nerf_mlp
    _assert_keeps_its_name("fused_nerf_mlp", "decode", one_chip)
