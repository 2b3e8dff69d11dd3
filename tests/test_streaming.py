"""Memory-centric streaming (§IV-A): RIT, MVoxel tables, exact equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import streaming
from repro.nerf import grids

CFG = streaming.StreamingCfg(grid_res=48, mvoxel_edge=8, capacity=256)


@pytest.fixture(scope="module")
def pts():
    return jax.random.uniform(jax.random.key(3), (4000, 3), minval=-1,
                              maxval=1)


@pytest.fixture(scope="module")
def table():
    return jax.random.normal(jax.random.key(4), (CFG.grid_res**3, 8))


def test_streaming_gather_exact(table, pts):
    ids, w = grids.corner_ids_weights(pts, CFG.grid_res)
    ref = grids.gather_trilerp_ref(table, ids, w)
    got, order = streaming.streaming_gather(table, pts, CFG)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # the order really is memory-centric: mvoxel ids non-decreasing
    mv = np.asarray(streaming.mvoxel_ids(pts, CFG))
    assert np.all(np.diff(mv[np.asarray(order)]) >= 0)


def _check_ragged(rit, key, num_keys, block):
    """Structural checks of a ragged RIT against its keys [S]."""
    key = np.asarray(key)
    sample = np.asarray(rit.sample)
    col = np.asarray(rit.col)
    block_key = np.asarray(rit.block_key)
    n_blocks = sample.shape[0]
    n_live = int(rit.n_live[0])
    live = key < num_keys
    # the static bound holds and the keys never go back
    assert n_blocks == streaming.rit_num_blocks(key.size, num_keys, block)
    assert n_live <= n_blocks
    assert np.all(np.diff(block_key) >= 0)
    assert np.all(block_key[n_live:] == block_key[max(n_live - 1, 0)])
    # every live sample has exactly one column, in a block of its key
    flat = sample.reshape(-1)
    held = flat[flat >= 0]
    assert len(np.unique(held)) == len(held) == int(live.sum())
    assert int(rit.live) == int(live.sum())
    assert np.array_equal(np.sort(held), np.flatnonzero(live))
    assert np.all(flat[col[live]] == np.flatnonzero(live))
    assert np.all(block_key[col[live] // block] == key[live])
    # dropped samples take none; only live blocks hold samples
    assert np.all(col[~live] == n_blocks * block)
    assert np.all(sample[n_live:] == -1)
    # each live block holds at least one sample (whole runs, pads at ends)
    assert np.all((sample[:n_live] >= 0).any(axis=1))


def test_rit_covers_every_sample_once(pts):
    mv = streaming.mvoxel_ids(pts, CFG)
    rit = streaming.build_rit(mv, CFG.num_mvoxels, CFG.capacity)
    _check_ragged(rit, mv, CFG.num_mvoxels, CFG.capacity)
    assert int(rit.live) == pts.shape[0]
    # every block only holds samples of its own mvoxel
    vals = np.asarray(rit.sample)
    mv_np = np.asarray(mv)
    for b in range(int(rit.n_live[0])):
        s = vals[b][vals[b] >= 0]
        assert np.all(mv_np[s] == int(rit.block_key[b]))


def test_rit_capacity_overflow():
    """A bucket of 100 samples piled in one voxel at 16 columns per block
    fills 7 blocks instead of spilling: no sample is left out."""
    pts = jnp.zeros((100, 3))
    cfg = streaming.StreamingCfg(grid_res=48, mvoxel_edge=8, capacity=16)
    mv = streaming.mvoxel_ids(pts, cfg)
    rit = streaming.build_rit(mv, cfg.num_mvoxels, cfg.capacity)
    _check_ragged(rit, mv, cfg.num_mvoxels, cfg.capacity)
    assert int(rit.n_live[0]) == 7
    assert int((np.asarray(rit.sample) >= 0).sum()) == 100
    assert int((np.asarray(rit.sample[6]) >= 0).sum()) == 100 - 6 * 16
    # and the kernel gathers every one of them exactly
    from repro.kernels import ops

    pts = pts + jax.random.uniform(jax.random.key(5), (100, 3),
                                   minval=-0.02, maxval=0.0)
    assert np.all(np.asarray(streaming.mvoxel_ids(pts, cfg)) == int(mv[0]))
    table = jax.random.normal(jax.random.key(6), (cfg.grid_res**3, 4))
    got = ops.gather_features_streaming(table, pts, cfg, interpret=True)
    ids, w = grids.corner_ids_weights(pts, cfg.grid_res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        grids.gather_trilerp_ref(table, ids, w)), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("num_keys,block,dropped", [
    (27, 16, 0.0), (27, 16, 0.3), (216, 8, 0.5), (8, 64, 0.9), (64, 4, 1.0),
])
def test_rit_drops_padding_and_keeps_its_bound(num_keys, block, dropped):
    """Keys past ``num_keys`` (chunk padding) take no column, ``block_key``
    never decreases and ``n_blocks`` is the static worst case, over random
    keys, piled keys and all-dropped streams."""
    rng = np.random.RandomState(num_keys + block)
    s = 700
    key = rng.randint(0, num_keys, size=s)
    key[: s // 5] = rng.randint(0, 2)  # a pile on one or two keys
    key[rng.uniform(size=s) < dropped] = num_keys + rng.randint(0, 3)
    rit = streaming.build_rit(jnp.asarray(key, jnp.int32), num_keys, block)
    _check_ragged(rit, key, num_keys, block)
    blocks = sum(-(-int(c) // block) for c in np.bincount(
        key[key < num_keys], minlength=num_keys))
    assert int(rit.n_live[0]) == blocks


def test_mvoxel_table_halo_equivalence(table, pts):
    mvt = streaming.build_mvoxel_table(table, CFG)
    assert mvt.shape == (CFG.num_mvoxels, CFG.halo_points, table.shape[-1])
    mv = streaming.mvoxel_ids(pts, CFG)
    lids, lw = streaming.local_corner_ids(pts, CFG)
    feats = jnp.einsum("svc,sv->sc", mvt[mv[:, None], lids], lw)
    gids, gw = grids.corner_ids_weights(pts, CFG.grid_res)
    ref = grids.gather_trilerp_ref(table, gids, gw)
    np.testing.assert_allclose(np.asarray(feats), np.asarray(ref), atol=1e-5)


def test_streaming_traffic_is_fully_sequential(pts):
    mv = np.asarray(streaming.mvoxel_ids(pts, CFG))
    stats = streaming.streaming_traffic(mv, CFG, channels=8)
    assert stats["non_streaming_fraction"] == 0.0
    assert stats["mvoxels_touched"] <= CFG.num_mvoxels


def test_pixel_centric_traffic_is_irregular():
    """Pixel-order vertex access through a small cache: mostly non-streaming
    (paper Fig. 4: >81% non-streaming on real models)."""
    from repro.nerf import models, rays, scenes

    scene = scenes.make_scene("drums")
    model, _ = models.make_model("dvgo", grid_res=48, channels=4,
                                 decoder="direct", num_samples=24)
    cam = rays.Camera.square(24)
    o, d = rays.generate_rays(cam, rays.orbit_pose(jnp.asarray(0.2)))
    pts, _ = rays.sample_along_rays(o, d, 0.5, 6.0, 24)
    stats = streaming.pixel_centric_traffic(
        np.asarray(pts.reshape(-1, 3)), res=48, channels=4,
        cache_bytes=64 * 1024)
    assert stats["non_streaming_fraction"] > 0.5
    assert stats["miss_rate"] > 0.02
