"""The flat ray-batch execution core: flat warp/compaction parity with the
per-frame primitives, fused flat NeRF calls vs exclusive runs, segment-aware
streaming gather, multi-device session sharding (bit parity in a 2-device
subprocess), ragged-window flat packing, and the transfer-free steady state."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline, raybatch, sparw
from repro.core.config import RenderConfig, ShardConfig
from repro.core.engine import DeviceSparwEngine
from repro.nerf import models, rays


@pytest.fixture(scope="module")
def small_model(scene):
    model, _ = models.make_model("dvgo", grid_res=32, channels=4,
                                 decoder="direct", num_samples=16)
    return model, model.init_baked(scene)


@pytest.fixture(scope="module")
def cam():
    return rays.Camera.square(32)


def _trajs(n_sessions, n_frames, step_deg=1.0):
    return [pipeline.orbit_trajectory(n_frames, step_deg=step_deg,
                                      phase_deg=25.0 * i)
            for i in range(n_sessions)]


# ---------------------------------------------------------------------------
# flat primitives vs their per-frame counterparts
# ---------------------------------------------------------------------------


def test_warp_frames_flat_matches_warp_frame(small_model, cam):
    """Every [s, n] slice of the flat warp pass bit-matches the per-frame
    warp_frame — same geometry, same z-buffer winners, same holes."""
    model, params = small_model
    trajs = _trajs(2, 3, step_deg=3.0)
    ref_poses = jnp.stack([t[0] for t in trajs])
    tgt_poses = jnp.stack([jnp.stack(t) for t in trajs])
    rgb_ref, dep_ref = [], []
    for t in trajs:
        rgb, dep = model.render_image(params, cam, t[0])
        rgb_ref.append(rgb)
        dep_ref.append(dep)
    rgb_ref, dep_ref = jnp.stack(rgb_ref), jnp.stack(dep_ref)

    flat = jax.jit(lambda *a: sparw.warp_frames_flat(*a, cam, phi_deg=4.0))(
        rgb_ref, dep_ref, ref_poses, tgt_poses)
    one_jit = jax.jit(lambda *a: sparw.warp_frame(*a, cam, phi_deg=4.0))
    for s in range(2):
        for n in range(3):
            one = one_jit(rgb_ref[s], dep_ref[s], ref_poses[s],
                          tgt_poses[s, n])
            np.testing.assert_array_equal(np.asarray(flat.holes[s, n]),
                                          np.asarray(one.holes))
            np.testing.assert_array_equal(np.asarray(flat.rgb[s, n]),
                                          np.asarray(one.rgb))
            np.testing.assert_array_equal(np.asarray(flat.depth[s, n]),
                                          np.asarray(one.depth))
            np.testing.assert_array_equal(np.asarray(flat.warp_angle[s, n]),
                                          np.asarray(one.warp_angle))


def test_pack_hole_rays_addresses(cam):
    """Flat hole packing gathers exactly the compacted rays and emits
    (session, frame)-major scatter addresses."""
    s, n, cap = 2, 2, 8
    hw = cam.height * cam.width
    rng = np.random.RandomState(0)
    idx = jnp.asarray(rng.randint(0, hw, size=(s, n, cap)), jnp.int32)
    poses = jnp.stack([jnp.stack(t) for t in _trajs(s, n)])
    batch, addr = raybatch.pack_hole_rays(cam, poses, idx)
    assert batch.origins.shape == (s * n * cap, 3)
    assert batch.seg.shape == (s * n * cap,)
    o_all, d_all = rays.generate_rays_batch(cam, poses.reshape(-1, 4, 4))
    for row in range(s * n * cap):
        b, c = divmod(row, cap)
        pix = int(idx.reshape(s * n, cap)[b, c])
        assert int(addr[row]) == b * hw + pix
        assert int(batch.seg[row]) == b // n
        np.testing.assert_array_equal(np.asarray(batch.dirs[row]),
                                      np.asarray(d_all[b, pix]))


def test_scatter_segments_drops_invalid():
    vals = jnp.asarray([[1.0, 1, 1], [2, 2, 2], [3, 3, 3]])
    addr = jnp.asarray([0, 5, 1], jnp.int32)
    valid = jnp.asarray([True, False, True])
    out = raybatch.scatter_segments(vals, addr, valid, 4)
    np.testing.assert_array_equal(
        np.asarray(out),
        [[1, 1, 1], [3, 3, 3], [0, 0, 0], [0, 0, 0]])


def test_segmented_streaming_gather_matches_per_segment(scene):
    """The cross-session gather returns exactly what per-segment gather
    calls return: a sample's output does not depend on the other
    sessions' samples that share its ragged RIT blocks."""
    from repro.core import streaming
    from repro.kernels import ops

    model, _ = models.make_model("dvgo", grid_res=32, channels=4,
                                 decoder="direct", backend="streaming",
                                 stream_capacity=64)
    params = model.prepare_streaming(model.init_baked(scene))
    cfg = model.streaming_cfg
    rng = np.random.RandomState(2)
    num_seg, per = 3, 500
    pts = jnp.asarray(rng.uniform(-0.9, 0.9, size=(num_seg * per, 3)),
                      jnp.float32)
    seg = jnp.repeat(jnp.arange(num_seg, dtype=jnp.int32), per)
    fused = ops.gather_features_streaming(
        params["table"], pts, cfg, mv_table=params["mv_table"],
        seg=seg, num_seg=num_seg)
    for i in range(num_seg):
        alone = ops.gather_features_streaming(
            params["table"], pts[i * per:(i + 1) * per], cfg,
            mv_table=params["mv_table"])
        np.testing.assert_array_equal(
            np.asarray(fused[i * per:(i + 1) * per]), np.asarray(alone))


def test_dump_segment_consumes_no_capacity(scene):
    """Chunk-padding rays (seg == num_seg) take no RIT column: a real
    segment's output is unchanged by appended dump-segment points."""
    from repro.kernels import ops

    model, _ = models.make_model("dvgo", grid_res=32, channels=4,
                                 decoder="direct", backend="streaming",
                                 stream_capacity=16)  # tiny: piles fill blocks
    params = model.prepare_streaming(model.init_baked(scene))
    cfg = model.streaming_cfg
    rng = np.random.RandomState(3)
    pts = jnp.asarray(rng.uniform(-0.5, 0.5, size=(400, 3)), jnp.float32)
    base = ops.gather_features_streaming(
        params["table"], pts, cfg, mv_table=params["mv_table"],
        seg=jnp.zeros(400, jnp.int32), num_seg=2)
    # pile dump-segment points onto the SAME coordinates
    padded_pts = jnp.concatenate([pts, pts], axis=0)
    padded_seg = jnp.concatenate([jnp.zeros(400, jnp.int32),
                                  jnp.full(400, 2, jnp.int32)])
    padded = ops.gather_features_streaming(
        params["table"], padded_pts, cfg, mv_table=params["mv_table"],
        seg=padded_seg, num_seg=2)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(padded[:400]))


# ---------------------------------------------------------------------------
# pooled tick-level hole capacity
# ---------------------------------------------------------------------------


def test_pooled_compaction_matches_per_frame_property():
    """Property test: whenever a session's window total fits the pool
    bucket (pool_cap >= counts.sum()), the pooled compaction enumerates
    exactly the per-frame ``compact_holes_flat`` samples — same pixels,
    same (session, frame) ownership, same order within each frame."""
    rng = np.random.RandomState(7)
    s, n, hw = 3, 4, 64
    for trial in range(20):
        density = rng.uniform(0.0, 0.6)
        holes = jnp.asarray(rng.rand(s, n, hw) < density)
        counts = np.asarray(holes.sum(axis=2))
        bucket = int(2 ** np.ceil(np.log2(max(counts.sum(axis=1).max(), 1))))
        assert bucket >= counts.sum(axis=1).max()
        addr, totals = sparw.compact_holes_pooled(holes, bucket)
        addr, totals = np.asarray(addr), np.asarray(totals)
        idx, _ = sparw.compact_holes_flat(holes, hw)  # cap=hw: lossless
        idx = np.asarray(idx)
        np.testing.assert_array_equal(totals, counts.sum(axis=1))
        for si in range(s):
            # expected: frame-major concatenation of each frame's compacted
            # pixels, as frame-local sample addresses n_i * hw + pixel
            expected = np.concatenate(
                [fi * hw + idx[si, fi, :counts[si, fi]]
                 for fi in range(n)])
            np.testing.assert_array_equal(addr[si, :totals[si]], expected)


def test_pooled_compaction_respects_window_mask():
    """Frames past a session's live window must not occupy pool slots."""
    s, n, hw = 2, 3, 32
    holes = jnp.ones((s, n, hw), bool)
    live = jnp.asarray([[True, True, False], [True, False, False]])
    addr, totals = sparw.compact_holes_pooled(holes, 128, live)
    np.testing.assert_array_equal(np.asarray(totals), [2 * hw, hw])
    assert int(np.asarray(addr)[0, :2 * hw].max()) < 2 * hw
    assert int(np.asarray(addr)[1, :hw].max()) < hw


def test_pool_overflow_isolated_per_session(small_model, cam):
    """One session exhausting ITS pool budget takes the dense fallback
    alone: the neighbour keeps sparse-path output bit-identical to a run
    where nobody overflowed."""
    model, params = small_model
    trajs = _trajs(2, 2, step_deg=6.0)
    ref_poses = jnp.stack([t[0] for t in trajs])
    tgt_poses = jnp.stack([jnp.stack(t) for t in trajs])
    eng = DeviceSparwEngine(model, params,
                            config=RenderConfig(camera=cam, window=2))
    bucket = eng.pool_ctl.max_bucket
    win_lens, caps = eng._staged_masks(2, 2)
    # control: both sessions comfortably inside the pool
    roomy = eng.render_windows(
        ref_poses, tgt_poses, win_lens, caps,
        pool_caps=jnp.asarray([bucket, bucket], jnp.int32),
        pool_caps_coarse=jnp.zeros(2, jnp.int32),
        bucket=bucket, bucket_coarse=0)
    totals = np.asarray(roomy.hole_counts).sum(axis=1)
    assert totals.min() > 0, "fixture must disocclude in both sessions"
    assert not np.asarray(roomy.overflowed).any()
    # starve session 0's pool budget only (traced input — no recompile)
    starved = eng.render_windows(
        ref_poses, tgt_poses, win_lens, caps,
        pool_caps=jnp.asarray([int(totals[0]) - 1, bucket], jnp.int32),
        pool_caps_coarse=jnp.zeros(2, jnp.int32),
        bucket=bucket, bucket_coarse=0)
    np.testing.assert_array_equal(np.asarray(starved.overflowed),
                                  [True, False])
    # neighbour: bit-identical sparse output; victim: dense != sparse run
    np.testing.assert_array_equal(np.asarray(starved.frames[1]),
                                  np.asarray(roomy.frames[1]))
    np.testing.assert_array_equal(np.asarray(starved.hole_counts),
                                  np.asarray(roomy.hole_counts))


def test_pooled_engine_bit_matches_unpooled(small_model, cam):
    """pool_holes=True (default) vs pool_holes=False over a trajectory:
    bit-identical frames — pooling changes WHERE hole rays sit in the
    batch, never their math (the fill chunks at a bucket-independent
    quantum, so XLA compiles the same per-ray loop body)."""
    model, params = small_model
    traj = pipeline.orbit_trajectory(6, step_deg=2.0)
    pooled = DeviceSparwEngine(model, params,
                               config=RenderConfig(camera=cam, window=2))
    legacy = DeviceSparwEngine(model, params, config=RenderConfig(
        camera=cam, window=2, pool_holes=False))
    fp, sp = pooled.render_trajectory(traj)
    fl, sl = legacy.render_trajectory(traj)
    assert len(fp) == len(fl)
    for a, b in zip(fp, fl):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sp.sparse_pixels == sl.sparse_pixels
    assert sp.fallback_pixels == sl.fallback_pixels


# ---------------------------------------------------------------------------
# ragged-window flat packing parity (PR 4 per-session overrides)
# ---------------------------------------------------------------------------


def test_ragged_windows_flat_pack_bit_parity(small_model, cam):
    """Mixed per-session win_lens/caps batch through the one flat program
    and every session still bit-matches its exclusive run."""
    model, params = small_model
    trajs = _trajs(3, 2, step_deg=2.0)
    cfg = RenderConfig(camera=cam, window=2)
    eng = DeviceSparwEngine(model, params, config=cfg)
    ref_poses = jnp.stack([t[0] for t in trajs])
    tgt_poses = jnp.stack([jnp.stack(t) for t in trajs])
    # session 0: full window; session 1: window 1 (padded); session 2:
    # half the hole capacity
    win_lens = jnp.asarray([2, 1, 2], jnp.int32)
    caps = jnp.asarray([eng.hole_cap, eng.hole_cap, eng.hole_cap // 2],
                       jnp.int32)
    batched = eng.render_windows(ref_poses, tgt_poses, win_lens, caps)
    for s, (win, cap) in enumerate([(2, None), (1, None),
                                    (2, eng.hole_cap // 2)]):
        solo = DeviceSparwEngine(model, params, config=RenderConfig(
            camera=cam, window=win, hole_cap=cap))
        res = solo.render_window(trajs[s][0], tgt_poses[s][:win])
        for j in range(win):
            np.testing.assert_array_equal(np.asarray(batched.frames[s, j]),
                                          np.asarray(res.frames[j]))


def test_flat_steady_state_tick_is_transfer_free(small_model, cam):
    """A warmed flat-packed render_windows tick runs under
    jax.transfer_guard('disallow') — packing, segment scatter and the S=1
    unwrap all stay on device."""
    model, params = small_model
    trajs = _trajs(2, 2)
    eng = DeviceSparwEngine(model, params,
                            config=RenderConfig(camera=cam, window=2))
    ref_poses = jnp.stack([t[0] for t in trajs])
    tgt_poses = jnp.stack([jnp.stack(t) for t in trajs])
    res = eng.render_windows(ref_poses, tgt_poses)  # warm-up
    jax.block_until_ready(res.frames)
    with jax.transfer_guard("disallow"):
        res2 = eng.render_windows(ref_poses, tgt_poses)
        jax.block_until_ready(res2.frames)
    np.testing.assert_array_equal(np.asarray(res.frames),
                                  np.asarray(res2.frames))


# ---------------------------------------------------------------------------
# multi-device session sharding
# ---------------------------------------------------------------------------


def test_shard_config_validation():
    with pytest.raises(ValueError):
        ShardConfig(num_devices=0)
    with pytest.raises(ValueError):
        # sessions must divide evenly over devices
        RenderConfig(num_slots=3, shard=ShardConfig(num_devices=2))
    cfg = RenderConfig(num_slots=4, shard=ShardConfig(num_devices=2))
    assert cfg.shard.enabled
    assert not ShardConfig().enabled
    # shard participates in config hashing / fingerprinting
    assert cfg.fingerprint() != cfg.replace(shard=None).fingerprint()


def test_shard_requires_enough_devices():
    """Asking for more devices than visible fails loudly, not silently."""
    ndev = jax.device_count()
    with pytest.raises(ValueError):
        raybatch.make_mesh(ShardConfig(num_devices=ndev + 1))
    assert raybatch.make_mesh(None) is None
    assert raybatch.make_mesh(ShardConfig(num_devices=1)) is None


def test_sharded_matches_unsharded_two_devices(tmp_path):
    """Sharded (2 CPU devices) vs unsharded render_windows: bit parity.
    Runs in a subprocess because the main pytest process is pinned to one
    device (XLA_FLAGS must be set before JAX initializes)."""
    code = """
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.core import pipeline
    from repro.core.config import RenderConfig, ShardConfig
    from repro.core.engine import DeviceSparwEngine
    from repro.nerf import models, rays, scenes

    assert jax.device_count() >= 2, jax.devices()
    scene = scenes.make_scene("lego")
    model, _ = models.make_model("dvgo", grid_res=32, channels=4,
                                 decoder="direct", num_samples=16)
    params = model.init_baked(scene)
    cam = rays.Camera.square(32)
    trajs = [pipeline.orbit_trajectory(4, step_deg=1.0, phase_deg=25.0 * i)
             for i in range(2)]
    ref_poses = jnp.stack([t[0] for t in trajs])
    tgt_poses = jnp.stack([jnp.stack(t[:2]) for t in trajs])

    base = DeviceSparwEngine(model, params,
                             config=RenderConfig(camera=cam, window=2))
    r0 = base.render_windows(ref_poses, tgt_poses)
    sh = DeviceSparwEngine(model, params, config=RenderConfig(
        camera=cam, window=2, num_slots=2, shard=ShardConfig(num_devices=2)))
    r1 = sh.render_windows(ref_poses, tgt_poses)
    assert len(r1.frames.sharding.device_set) == 2, r1.frames.sharding
    np.testing.assert_array_equal(np.asarray(r0.frames),
                                  np.asarray(r1.frames))
    np.testing.assert_array_equal(np.asarray(r0.hole_counts),
                                  np.asarray(r1.hole_counts))
    np.testing.assert_array_equal(np.asarray(r0.overflowed),
                                  np.asarray(r1.overflowed))
    print("SHARDED_PARITY_OK")
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu", PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=600)
    if r.returncode != 0 and "device_count" in r.stderr and \
            "assert" not in r.stderr.lower():
        pytest.skip(f"2 host devices unavailable: {r.stderr[-500:]}")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_PARITY_OK" in r.stdout
