"""Unified streaming tick: fused dual-stage gather, MVoxel bank layout,
cross-tick pipelined trajectory parity, and bytes-moved accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import streaming
from repro.core.config import RenderConfig
from repro.kernels import ops, streaming_pipeline
from repro.nerf import grids

CFG_I = streaming.StreamingCfg(grid_res=16, mvoxel_edge=8, capacity=128,
                               layout="identity")
CFG_B = dataclasses.replace(CFG_I, layout="bank_interleaved")


@pytest.fixture(scope="module")
def table():
    return jax.random.normal(jax.random.key(7), (CFG_I.grid_res**3, 4))


@pytest.fixture(scope="module")
def pts():
    return jax.random.uniform(jax.random.key(8), (600, 3), minval=0.02,
                              maxval=0.98)


# ---------------------------------------------------------------------------
# bank-interleaved layout
# ---------------------------------------------------------------------------


def test_layout_row_map_is_permutation_into_banked_rows():
    rows, padded = streaming.layout_row_map(CFG_B)
    p = CFG_B.halo_points
    assert rows.shape == (p,)
    assert padded == CFG_B.halo_rows >= p
    # injective (a permutation into the padded row space)
    assert len(np.unique(rows)) == p
    # the defining property: physical row index mod num_banks IS the
    # point's bank, so same-bank points never share a bank row
    banks = streaming.halo_point_banks(CFG_B)
    assert np.array_equal(rows % CFG_B.num_banks, banks)


def test_voxel_corners_hit_all_banks():
    # the 8 corners of ANY voxel (offsets in {0,1}^3) map to 8 distinct
    # banks under (4x + 2y + z) mod 8 — the conflict-free guarantee
    banks = streaming.halo_point_banks(CFG_B).reshape(
        CFG_B.mvoxel_edge + 1, CFG_B.mvoxel_edge + 1, CFG_B.mvoxel_edge + 1)
    e = CFG_B.mvoxel_edge
    for x in range(e):
        for y in range(e):
            corner_banks = {int(banks[x + a, y + b, z + c])
                            for z in range(1)
                            for a in (0, 1) for b in (0, 1) for c in (0, 1)}
            assert len(corner_banks) == 8


def test_bank_conflict_factor():
    # identity raster order stacks corners into shared banks; the
    # interleaved layout is conflict-free by construction
    assert streaming.bank_conflict_factor(CFG_B) == 1.0
    assert streaming.bank_conflict_factor(CFG_I) > 1.0


def test_layout_bit_identical_staged_gather(table, pts):
    mv_i = streaming.build_mvoxel_table(table, CFG_I)
    mv_b = streaming.build_mvoxel_table(table, CFG_B)
    f_i = ops.gather_features_streaming(table, pts, CFG_I, mv_table=mv_i,
                                        interpret=True)
    f_b = ops.gather_features_streaming(table, pts, CFG_B, mv_table=mv_b,
                                        interpret=True)
    # the layout is a pure row permutation of the one-hot gather — outputs
    # are bit-identical, not merely close (the parity control the bench
    # gates on)
    np.testing.assert_array_equal(np.asarray(f_i), np.asarray(f_b))
    ids, w = grids.corner_ids_weights(pts, CFG_I.grid_res)
    ref = grids.gather_trilerp_ref(table, ids, w)
    np.testing.assert_allclose(np.asarray(f_i), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# fused dual-stage gather
# ---------------------------------------------------------------------------


def test_fused_gather_matches_reference_both_sets(table, pts):
    """The tick's merged stream returns both sets equal to the reference
    gather, and its padding counts close: pads + live samples = columns."""
    seg = jnp.concatenate([jnp.zeros(300, jnp.int32),
                           jnp.ones(300, jnp.int32)])
    ids, w = grids.corner_ids_weights(pts, CFG_I.grid_res)
    ref = np.asarray(grids.gather_trilerp_ref(table, ids, w))
    for cfg in (CFG_I, CFG_B):
        mv = streaming.build_mvoxel_table(table, cfg)
        fh, fr, pad, cols = streaming_pipeline.gather_features_tick(
            mv, cfg, pts, seg, pts, seg, num_seg=2, interpret=True)
        np.testing.assert_allclose(np.asarray(fh), ref, atol=1e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(fr), ref, atol=1e-5,
                                   rtol=1e-5)
        assert int(cols) % cfg.capacity == 0
        assert int(cols) - int(pad) == 2 * pts.shape[0]


def test_fused_gather_layout_bit_identical(table, pts):
    seg = jnp.zeros(pts.shape[0], jnp.int32)
    outs = []
    for cfg in (CFG_I, CFG_B):
        mv = streaming.build_mvoxel_table(table, cfg)
        outs.append(streaming_pipeline.gather_features_tick(
            mv, cfg, pts, seg, pts, seg, num_seg=1, interpret=True))
    np.testing.assert_array_equal(np.asarray(outs[0][0]),
                                  np.asarray(outs[1][0]))
    np.testing.assert_array_equal(np.asarray(outs[0][1]),
                                  np.asarray(outs[1][1]))


def test_fused_gather_piled_bucket_matches_reference(table, pts):
    """100 samples piled into one MVoxel at 16 columns per block fill 7
    blocks of the merged stream; both sets still match the reference
    gather, and dropped padding (seg == num_seg) takes no column."""
    small = dataclasses.replace(CFG_I, capacity=16)
    mv = streaming.build_mvoxel_table(table, small)
    piled = jnp.concatenate([jnp.full((100, 3), 0.1), pts[:50]])
    seg = jnp.zeros(piled.shape[0], jnp.int32)
    dump = jnp.full(piled.shape[0], 1, jnp.int32)
    ids, w = grids.corner_ids_weights(piled, small.grid_res)
    ref = np.asarray(grids.gather_trilerp_ref(table, ids, w))
    fh, fr, pad, cols = streaming_pipeline.gather_features_tick(
        mv, small, piled, seg, piled, seg, num_seg=1, interpret=True)
    np.testing.assert_allclose(np.asarray(fh), ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fr), ref, atol=1e-5, rtol=1e-5)
    # the pile is 200 samples of one MVoxel in the merged stream: 13 blocks
    key = streaming.mvoxel_ids(jnp.concatenate([piled, piled]), small)
    rit = streaming.build_rit(key, small.num_mvoxels, small.capacity)
    assert int(jnp.sum(rit.block_key[:int(rit.n_live[0])] == key[0])) >= 13
    assert int(cols) == int(rit.n_live[0]) * 16
    # the reference set dropped: its rows read zero, the holes' are kept
    fh2, fr2, pad2, cols2 = streaming_pipeline.gather_features_tick(
        mv, small, piled, seg, piled, dump, num_seg=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(fh2), np.asarray(fh))
    assert not np.asarray(fr2).any()
    assert int(cols2) - int(pad2) == piled.shape[0]


def test_sample_output_independent_of_its_block_mates(table, pts):
    """A sample's features are bit-identical whatever samples share its
    RIT block: alone, beside the other half of the set, or with a pile in
    its MVoxel that moves it to another block and column."""
    cfg = dataclasses.replace(CFG_I, capacity=16)
    mv = streaming.build_mvoxel_table(table, cfg)
    probe = pts[:40]

    def feats(extra):
        both = jnp.concatenate([probe, extra])
        f = ops.gather_features_streaming(table, both, cfg, mv_table=mv,
                                          interpret=True)
        return np.asarray(f[:40])

    alone = feats(jnp.zeros((0, 3)))
    np.testing.assert_array_equal(alone, feats(pts[40:]))
    np.testing.assert_array_equal(alone, feats(jnp.repeat(probe, 3, 0)))
    np.testing.assert_array_equal(alone, feats(probe[::-1]))


def test_mixed_scene_tick_reads_each_segments_page(table, pts):
    """On the mixed-scene path the merged stream keys (page, MVoxel) over
    the stacked table: each segment's rows equal its own scene's
    exclusive gather, bit for bit."""
    other = table[::-1] * 0.5
    mv_a = streaming.build_mvoxel_table(table, CFG_I)
    mv_b = streaming.build_mvoxel_table(other, CFG_I)
    stacked = jnp.stack([mv_a, mv_b])
    seg = jnp.concatenate([jnp.zeros(300, jnp.int32),
                           jnp.ones(300, jnp.int32)])
    scene_of_seg = jnp.array([1, 0], jnp.int32)
    fh, fr, _, _ = streaming_pipeline.gather_features_tick(
        stacked, CFG_I, pts, seg, pts[::-1], seg, num_seg=2,
        scene_of_seg=scene_of_seg, interpret=True)
    for got, p, scene in ((fh[:300], pts[:300], mv_b),
                          (fh[300:], pts[300:], mv_a),
                          (fr[:300], pts[::-1][:300], mv_b),
                          (fr[300:], pts[::-1][300:], mv_a)):
        alone = ops.gather_features_streaming(None, p, CFG_I,
                                              mv_table=scene,
                                              interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))


# ---------------------------------------------------------------------------
# end-to-end: fused trajectory vs staged trajectory
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tick_setup():
    from repro import api
    from repro.core import pipeline

    base = dict(scene="lego", res=24, window=2, grid_res=16, channels=4,
                decoder="direct", num_samples=8, backend="streaming",
                pool_holes=True, pallas_interpret=True)
    cfg_staged = RenderConfig(**base).resolved()
    cfg_fused = cfg_staged.replace(fused_tick=True)
    r = api.make_renderer(cfg_staged)
    poses = pipeline.orbit_trajectory(4, step_deg=4.0)
    return r, cfg_staged, cfg_fused, poses


def test_fused_trajectory_matches_staged(tick_setup):
    from repro.core.engine import DeviceSparwEngine
    from repro.utils import psnr

    r, cfg_staged, cfg_fused, poses = tick_setup
    eng_s = DeviceSparwEngine(r.model, r.params, config=cfg_staged)
    eng_f = DeviceSparwEngine(r.model, r.params, config=cfg_fused)
    fs, st_s = eng_s.render_trajectory(poses)
    ff, st_f = eng_f.render_trajectory(poses)
    assert len(fs) == len(ff) == len(poses)
    # same warp geometry => identical hole statistics; the fill values run
    # through the same gather math (fused vs chunked), so frames agree to
    # float precision
    assert st_s.hole_fractions == st_f.hole_fractions
    for a, b in zip(fs, ff):
        assert float(psnr(a, b)) >= 60.0


def test_fused_trajectory_layout_bit_identical(tick_setup):
    from repro.nerf import models as nmodels
    from repro.core.engine import DeviceSparwEngine

    r, _, cfg_fused, poses = tick_setup
    lay_model = nmodels.NerfModel(
        dataclasses.replace(r.model.cfg, mvoxel_layout="bank_interleaved"),
        scene=r.model.scene)
    eng_i = DeviceSparwEngine(r.model, r.params, config=cfg_fused)
    eng_b = DeviceSparwEngine(lay_model, r.params,
                              config=cfg_fused.replace(
                                  mvoxel_layout="bank_interleaved"))
    fi, _ = eng_i.render_trajectory(poses)
    fb, _ = eng_b.render_trajectory(poses)
    for a, b in zip(fi, fb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# bytes-moved accounting
# ---------------------------------------------------------------------------


def test_tick_memory_stats_sweep_math(tick_setup):
    from repro.core.engine import DeviceSparwEngine

    r, cfg_staged, _, _ = tick_setup
    eng = DeviceSparwEngine(r.model, r.params, config=cfg_staged)
    mem = eng.tick_memory_stats(sessions=2, window=2)
    # the fused path is one sweep by construction; the staged path is
    # ref chunks + fill chunks, each >= 2 (the flat core's trip-count
    # invariant), so the reduction is always >= 4x here
    assert mem["fused_table_sweeps_per_tick"] == 1.0
    assert mem["staged_ref_sweeps"] >= 2.0
    assert mem["staged_fill_sweeps"] >= 2.0
    assert mem["staged_table_sweeps_per_tick"] == \
        mem["staged_ref_sweeps"] + mem["staged_fill_sweeps"]
    assert mem["bytes_reduction_staged_over_fused"] == \
        mem["staged_table_sweeps_per_tick"]
    # bytes are sweeps x full-table bytes, normalized per frame
    scfg = r.model.streaming_cfg
    table_bytes = scfg.num_mvoxels * scfg.halo_rows * 4 * 4
    assert mem["mvoxel_table_bytes"] == table_bytes
    assert mem["fused_mvoxel_bytes_per_frame"] == table_bytes / 4


def test_tick_traffic_analytic_counts():
    samples = 2 * (128 + 256) * 8
    traffic = streaming_pipeline.tick_traffic(CFG_I, channels=4,
                                              samples=samples)
    num_mv = CFG_I.num_mvoxels
    assert traffic["mvoxel_table_sweeps"] == 1.0
    assert traffic["mvoxel_table_bytes"] == num_mv * CFG_I.halo_rows * 4 * 4
    # RIT side: ids+weights in, features out, per column of the ragged
    # RIT's static block bound (ceil(samples / T) + num_mv blocks)
    blocks = -(-samples // CFG_I.capacity) + num_mv
    assert traffic["rit_bytes"] == blocks * CFG_I.capacity * (8 * 8 + 4 * 4)
    assert traffic["total_bytes"] == \
        traffic["mvoxel_table_bytes"] + traffic["rit_bytes"]
    # the bound grows with the samples, not with segments x MVoxels x caps
    more = streaming_pipeline.tick_traffic(CFG_I, channels=4,
                                           samples=4 * samples)
    assert more["rit_bytes"] < 4 * traffic["rit_bytes"]


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def test_fused_tick_config_validation():
    with pytest.raises(ValueError, match="backend"):
        RenderConfig(fused_tick=True, backend="reference")
    with pytest.raises(ValueError, match="pool_holes"):
        RenderConfig(fused_tick=True, backend="streaming",
                     pool_holes=False)
    with pytest.raises(ValueError, match="adaptive"):
        RenderConfig(fused_tick=True, backend="streaming",
                     adaptive_sampling=True)
    with pytest.raises(ValueError, match="mvoxel_layout"):
        RenderConfig(mvoxel_layout="diagonal")
