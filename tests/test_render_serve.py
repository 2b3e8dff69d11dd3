"""Multi-session SpaRW serving engine: batched-vs-sequential parity, ragged
session lifetimes (slot reuse), per-session overflow isolation, and the
zero-host-sync-per-tick contract (including mixed per-session windows)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline
from repro.core.config import RenderConfig
from repro.nerf import models, rays, scenes
from repro.serve.render_engine import (RenderServeEngine, RenderSession,
                                       delivery_latencies)
from repro.utils import psnr


@pytest.fixture(scope="module")
def small_model(scene):
    model, _ = models.make_model("dvgo", grid_res=32, channels=4,
                                 decoder="direct", num_samples=16)
    return model, model.init_baked(scene)


@pytest.fixture(scope="module")
def cam():
    return rays.Camera.square(32)


def _cfg(cam, **kw):
    return RenderConfig(camera=cam, **kw)


def _trajs(n_sessions, n_frames, step_deg=1.0):
    return [pipeline.orbit_trajectory(n_frames, step_deg=step_deg,
                                      phase_deg=25.0 * i)
            for i in range(n_sessions)]


def _single_session_frames(model, params, cam, traj, window, hole_cap=None):
    r = pipeline.CiceroRenderer(
        model, params, config=_cfg(cam, window=window, hole_cap=hole_cap))
    return r.render_trajectory(traj)


def test_model_batched_entry_points_match_per_session(small_model, cam):
    """render_rays_flat / render_image_batch: a fused session-major flat
    batch — each session's rows match the unbatched render of that pose."""
    model, params = small_model
    c2ws = jnp.stack(pipeline.orbit_trajectory(3, step_deg=40.0))
    col_b, dep_b = model.render_image_batch(params, cam, c2ws, chunk=256)
    assert col_b.shape == (3, cam.height, cam.width, 3)
    for i in range(3):
        col, dep = model.render_image(params, cam, c2ws[i])
        np.testing.assert_allclose(np.asarray(col_b[i]), np.asarray(col),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(dep_b[i]), np.asarray(dep),
                                   atol=1e-5)
    # the jitted flat renderer is built once per model
    assert model.render_rays_flat_jit is model.render_rays_flat_jit


def test_streamed_schedule_state_matches_batch_plan():
    """RefPoseExtrapolator fed window-by-window (the serving engine's view)
    emits bit-identical reference poses to WarpSchedule.windows on the
    whole trajectory (the planner's view), including a ragged tail."""
    from repro.core import schedule

    poses = pipeline.orbit_trajectory(11, step_deg=2.0, wobble=0.05)
    for window in (1, 2, 4):
        plan_refs = [w["ref_pose"] for w in
                     schedule.WarpSchedule(window, "offtraj").windows(poses)]
        state = schedule.RefPoseExtrapolator(window=window)
        for i, k in enumerate(range(0, len(poses), window)):
            ref = state.next_reference(poses[k:k + window])
            np.testing.assert_array_equal(np.asarray(ref),
                                          np.asarray(plan_refs[i]))


def test_batched_matches_sequential_single_session(small_model, cam):
    """Every session of a batched run receives exactly the frames (and
    work statistics) an exclusive single-session engine would produce."""
    model, params = small_model
    trajs = _trajs(3, 5)
    renderer = pipeline.CiceroRenderer(model, params,
                                       config=_cfg(cam, window=2))
    frames_b, stats_b, metrics = renderer.render_trajectories(trajs)
    assert metrics["total_frames"] == 15
    assert metrics["ticks"] == 3  # ceil(5/2) windows, all sessions in step
    for i, traj in enumerate(trajs):
        fs, ss = _single_session_frames(model, params, cam, traj, window=2)
        assert len(frames_b[i]) == len(fs)
        for a, b in zip(fs, frames_b[i]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert stats_b[i].frames == ss.frames
        assert stats_b[i].sparse_pixels == ss.sparse_pixels
        np.testing.assert_allclose(stats_b[i].hole_fractions,
                                   ss.hole_fractions, atol=1e-9)


def test_ragged_session_lifetimes_and_slot_reuse(small_model, cam):
    """Sessions of different lengths join and leave mid-run; a freed slot
    is reused by the next queued session; everyone still gets parity."""
    model, params = small_model
    lengths = [5, 2, 7, 3]
    trajs = [pipeline.orbit_trajectory(n, step_deg=1.0, phase_deg=20.0 * i)
             for i, n in enumerate(lengths)]
    serve = RenderServeEngine(model, params,
                              config=_cfg(cam, num_slots=2, window=2))
    sessions = [RenderSession(sid=i, poses=list(t))
                for i, t in enumerate(trajs)]
    metrics = serve.run(sessions)
    assert all(s.done for s in sessions)
    # 2 slots over 4 sessions: the engine must have queued + reused slots
    assert metrics["ticks"] > max((n + 1) // 2 for n in lengths)
    for sess, traj in zip(sessions, trajs):
        assert all(f is not None for f in sess.frames)
        fs, _ = _single_session_frames(model, params, cam, traj, window=2)
        for a, b in zip(fs, sess.frames):
            assert float(psnr(a, b)) >= 60.0
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_overflow_isolation_between_sessions(small_model, cam):
    """One session overflowing hole_cap (dense fallback) must not perturb
    its neighbour: the quiet session's frames stay bit-identical to its
    exclusive run and its stats never report dense work."""
    model, params = small_model
    hot = pipeline.orbit_trajectory(4, step_deg=25.0)  # violent motion
    quiet = pipeline.orbit_trajectory(4, step_deg=0.05, phase_deg=180.0)
    hw = cam.height * cam.width

    # pick a cap between the two sessions' hole regimes
    _, s_hot = _single_session_frames(model, params, cam, hot, window=2)
    _, s_quiet = _single_session_frames(model, params, cam, quiet, window=2)
    hot_max = int(max(s_hot.hole_fractions) * hw)
    quiet_max = int(max(s_quiet.hole_fractions) * hw)
    assert quiet_max < hot_max, "fixture trajectories must differ in motion"
    cap = max(quiet_max + 8, (quiet_max + hot_max) // 2)
    assert cap < hot_max

    serve = RenderServeEngine(
        model, params, config=_cfg(cam, num_slots=2, window=2, hole_cap=cap))
    sessions = [RenderSession(sid=0, poses=list(hot)),
                RenderSession(sid=1, poses=list(quiet))]
    serve.run(sessions)
    # hot session fell back to dense at least once (the fallback's extra
    # non-hole pixels land in fallback_pixels; sparse_pixels stays true)
    assert sessions[0].stats.fallback_pixels > 0
    assert sessions[0].stats.sparse_pixels == sum(
        int(f * hw) for f in sessions[0].stats.hole_fractions)
    # quiet session: sparse path only, stats record true hole counts
    assert sessions[1].stats.fallback_pixels == 0
    assert sessions[1].stats.sparse_pixels == sum(
        int(f * hw) for f in sessions[1].stats.hole_fractions)
    # ... and bit-identical frames to its exclusive run at the same cap
    fq, _ = _single_session_frames(model, params, cam, quiet, window=2,
                                   hole_cap=cap)
    for a, b in zip(fq, sessions[1].frames):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the hot session still gets correct frames (dense fallback output)
    fh, _ = _single_session_frames(model, params, cam, hot, window=2,
                                   hole_cap=cap)
    for a, b in zip(fh, sessions[0].frames):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tick_has_zero_host_syncs(small_model, cam):
    """A serving tick is dispatch-only: after warm-up, `step()` runs under
    ``jax.transfer_guard('disallow')`` — any device→host sync inside the
    tick would raise. Frames/stats materialize only in `finalize()`.
    Exercised on a MIXED-window batch: the per-session win_lens/caps
    arrays are staged at admit, so a steady-state ragged tick is still
    pure dispatch."""
    model, params = small_model
    trajs = _trajs(2, 6)
    serve = RenderServeEngine(model, params,
                              config=_cfg(cam, num_slots=2, window=2))
    serve.submit([RenderSession(sid=0, poses=list(trajs[0]), window=1),
                  RenderSession(sid=1, poses=list(trajs[1]))])
    assert serve.step()  # warm-up tick: trace + compile + mask staging
    jax.block_until_ready(serve._last_result.frames)
    with jax.transfer_guard("disallow"):
        assert serve.step()  # steady-state ragged tick: pure dispatch
        jax.block_until_ready(serve._last_result.frames)
    while serve.step():
        pass
    serve.finalize()
    # one batched device call per tick, materialization deferred to finalize
    assert serve.engine.num_window_calls == serve.num_ticks
    assert serve._pending == []


def test_single_compile_for_engine_lifetime(small_model, cam):
    """Fixed slots + pose padding keep the batch shape static: ragged
    trajectories, idle slots AND mixed per-session window/hole_cap
    overrides all reuse compiled programs (win_lens/caps/pool_caps are
    traced inputs — no per-tick or per-session retrace). With pooling the
    only extra compiles are pool-bucket ladder steps: exactly one program
    per distinct (bucket, bucket_coarse), bounded by the ladder size."""
    model, params = small_model
    trajs = [pipeline.orbit_trajectory(n, step_deg=1.0, phase_deg=10.0 * n)
             for n in (5, 3, 4)]  # ragged + an idle slot at the end
    serve = RenderServeEngine(model, params,
                              config=_cfg(cam, num_slots=3, window=2))
    sessions = [RenderSession(sid=0, poses=list(trajs[0])),
                RenderSession(sid=1, poses=list(trajs[1]), window=1),
                RenderSession(sid=2, poses=list(trajs[2]),
                              hole_cap=serve.engine.hole_cap // 2)]
    serve.run(sessions)
    assert all(s.done for s in sessions)
    compiles = serve.engine._windows_jit._cache_size()
    assert compiles == len(serve.engine.pool_buckets_used), \
        f"compiles ({compiles}) must track distinct pool buckets " \
        f"({serve.engine.pool_buckets_used})"
    assert compiles <= serve.engine.pool_ladder_size


def test_pool_disabled_is_single_compile(small_model, cam):
    """pool_holes=False restores the PR 5 contract verbatim: one compiled
    batch program for the whole engine lifetime."""
    model, params = small_model
    trajs = [pipeline.orbit_trajectory(n, step_deg=1.0, phase_deg=10.0 * n)
             for n in (5, 3)]
    serve = RenderServeEngine(
        model, params,
        config=_cfg(cam, num_slots=2, window=2, pool_holes=False))
    sessions = [RenderSession(sid=i, poses=list(t))
                for i, t in enumerate(trajs)]
    serve.run(sessions)
    assert all(s.done for s in sessions)
    compiles = serve.engine._windows_jit._cache_size()
    assert compiles == 1, f"expected 1 compiled batch program, got {compiles}"


def test_pool_resize_recompiles_bounded_by_ladder(small_model, cam):
    """A long steady run walks the hole-cap controller down the pow2
    ladder: the bucket actually shrinks (work reduction is real), every
    resize compiles at most one new program, and the total compile count
    stays <= the ladder size (satellite: recompile-count gate)."""
    model, params = small_model
    trajs = _trajs(2, 16)  # long enough for the EWMA to settle + resize
    serve = RenderServeEngine(model, params,
                              config=_cfg(cam, num_slots=2, window=2))
    sessions = [RenderSession(sid=i, poses=list(t))
                for i, t in enumerate(trajs)]
    serve.run(sessions)
    assert all(s.done for s in sessions)
    buckets = sorted(b for b, _ in serve.engine.pool_buckets_used)
    assert len(buckets) >= 2, "controller never resized the pool bucket"
    assert buckets[0] < serve.engine.pool_ctl.max_bucket
    compiles = serve.engine._windows_jit._cache_size()
    assert compiles == len(serve.engine.pool_buckets_used)
    assert compiles <= serve.engine.pool_ladder_size
    # a fixed per-session pool_bucket override pins the ladder to one rung
    pinned = RenderServeEngine(model, params,
                               config=_cfg(cam, num_slots=2, window=2))
    bmax = pinned.engine.pool_ctl.max_bucket
    psessions = [RenderSession(sid=i, poses=list(t), pool_bucket=bmax)
                 for i, t in enumerate(_trajs(2, 16))]
    pinned.run(psessions)
    assert pinned.engine.pool_buckets_used == {(bmax, 0)}


# ---------------------------------------------------------------------------
# submit() hygiene: duplicate sids, all-or-nothing validation
# ---------------------------------------------------------------------------


def test_duplicate_sid_rejected_among_live_sessions(small_model, cam):
    """Per-session metrics are keyed on sid, so two live sessions sharing
    one would silently collapse into a single metrics entry. submit()
    rejects duplicates within a batch and against queued/in-slot
    sessions; a COMPLETED session releases its sid for reuse."""
    model, params = small_model
    serve = RenderServeEngine(model, params,
                              config=_cfg(cam, num_slots=2, window=2))
    t = _trajs(1, 3)[0]
    with pytest.raises(ValueError, match="duplicates a live session"):
        serve.submit([RenderSession(sid=7, poses=list(t)),
                      RenderSession(sid=7, poses=list(t))])
    first = RenderSession(sid=7, poses=list(t))
    serve.submit([first])
    with pytest.raises(ValueError, match="duplicates a live session"):
        serve.submit([RenderSession(sid=7, poses=list(t))])  # vs queued
    serve.step()  # admit into a slot — still live
    with pytest.raises(ValueError, match="duplicates a live session"):
        serve.submit([RenderSession(sid=7, poses=list(t))])  # vs in-slot
    while serve.step():
        pass
    serve.finalize()
    assert first.done
    reuse = RenderSession(sid=7, poses=list(t))
    serve.run([reuse])  # sid released on completion
    assert reuse.done


def test_failed_submit_leaves_state_untouched(small_model, cam):
    """submit() validates the WHOLE batch before mutating anything: a
    rejected batch consumes no arrival stamps and leaves every session
    object exactly as the caller built it, so fixing the offender and
    resubmitting the same objects just works."""
    model, params = small_model
    serve = RenderServeEngine(model, params,
                              config=_cfg(cam, num_slots=2, window=2))
    t = _trajs(1, 3)[0]
    batch = [RenderSession(sid=0, poses=list(t)),
             RenderSession(sid=1, poses=list(t)),
             RenderSession(sid=2, poses=list(t), window=99)]  # invalid
    before = serve._num_submitted
    with pytest.raises(ValueError, match="window override"):
        serve.submit(batch)
    assert serve.queue == []
    assert serve._num_submitted == before
    for sess in batch:
        assert sess.arrival == -1 and sess.submitted_s is None
    batch[2].window = None  # fix the offender; resubmit the SAME objects
    metrics = serve.run(batch)
    assert metrics["complete"]
    assert [s.arrival for s in batch] == [0, 1, 2]


def test_reused_engine_recompile_accounting(small_model, cam):
    """run() reports the recompiles THIS run spent, not the engine's
    lifetime bucket set: a second fleet on a warm engine that stays on
    already-compiled ladder rungs must report zero."""
    model, params = small_model
    serve = RenderServeEngine(model, params,
                              config=_cfg(cam, num_slots=2, window=2))
    m1 = serve.run([RenderSession(sid=i, poses=list(t))
                    for i, t in enumerate(_trajs(2, 8))])
    assert m1["pool"]["recompiles"] >= 1  # cold engine compiled something
    lifetime = len(serve.engine.pool_buckets_used)
    m2 = serve.run([RenderSession(sid=i, poses=list(t))
                    for i, t in enumerate(_trajs(2, 8))])
    assert m2["complete"]
    # same trajectories walk the same ladder rungs: nothing new compiled,
    # and the per-run metric says so (lifetime count would not)
    assert len(serve.engine.pool_buckets_used) == lifetime
    assert m2["pool"]["recompiles"] == 0


# ---------------------------------------------------------------------------
# fused streaming serving (config.fused_tick through RenderServeEngine)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fused_setup():
    from repro import api

    base = dict(scene="lego", res=24, window=2, grid_res=16, channels=4,
                decoder="direct", num_samples=8, backend="streaming",
                pool_holes=True, pallas_interpret=True, num_slots=2)
    cfg_staged = RenderConfig(**base).resolved()
    cfg_fused = cfg_staged.replace(fused_tick=True)
    r = api.make_renderer(cfg_staged)
    return r, cfg_staged, cfg_fused


def test_fused_serving_matches_staged_serving(fused_setup):
    """The fused serving tick (single-sweep streaming pipeline + cross-tick
    reference recurrence + prime-on-admit) serves the same fleet as the
    staged path: identical hole statistics (same warp geometry) and
    float-precision frames, with slot reuse and queueing exercised."""
    r, cfg_staged, cfg_fused = fused_setup
    trajs = _trajs(3, 5, step_deg=4.0)  # 3 sessions over 2 slots
    st = RenderServeEngine(r.model, r.params, config=cfg_staged)
    fu = RenderServeEngine(r.model, r.params, config=cfg_fused)
    s_sess = [RenderSession(sid=i, poses=list(t))
              for i, t in enumerate(trajs)]
    f_sess = [RenderSession(sid=i, poses=list(t))
              for i, t in enumerate(trajs)]
    m_s = st.run(s_sess)
    m_f = fu.run(f_sess)
    assert m_s["complete"] and m_f["complete"]
    assert m_s["ticks"] == m_f["ticks"]
    for a, b in zip(s_sess, f_sess):
        assert a.stats.hole_fractions == b.stats.hole_fractions
        for fa, fb in zip(a.frames, b.frames):
            assert float(psnr(fa, fb)) >= 60.0
    # the serving-tick traffic accounting reflects the dispatched path
    assert m_f["memory"]["serving_path"] == "fused"
    assert m_f["memory"]["serving_table_sweeps_per_tick_steady"] == 1.0
    assert m_s["memory"]["serving_path"] == "staged"
    assert (m_s["memory"]["serving_table_sweeps_per_tick_steady"]
            == m_s["memory"]["staged_table_sweeps_per_tick"] > 2.0)
    # admission ticks (initial bootstrap + the slot-reuse admit) amortize
    # the prime's staged sweeps over the run; steady state stays at one
    assert m_f["memory"]["admission_ticks"] >= 2
    amort = m_f["memory"]["serving_table_sweeps_per_tick_amortized"]
    assert 1.0 < amort < m_s["memory"]["staged_table_sweeps_per_tick"]


def test_fused_serving_slot_reuse_reference_isolation(fused_setup):
    """Leak-proof slot reuse on the recurrence: session B admitted into
    A's drained slot gets BIT-IDENTICAL frames to its exclusive fused
    run — prime-on-admit overwrites every lane of the reused row
    (masked row select), so no trace of A's reference radiance can
    reach B through the cross-tick reference arrays."""
    r, _, cfg_fused = fused_setup
    cfg = cfg_fused.replace(num_slots=1)  # B MUST reuse A's slot
    t_a = pipeline.orbit_trajectory(4, step_deg=25.0)        # far from B
    t_b = pipeline.orbit_trajectory(4, step_deg=4.0, phase_deg=180.0)
    shared = RenderServeEngine(r.model, r.params, config=cfg)
    a = RenderSession(sid=0, poses=list(t_a))
    b = RenderSession(sid=1, poses=list(t_b))
    shared.run([a, b])
    assert a.done and b.done
    exclusive = RenderServeEngine(r.model, r.params, config=cfg)
    b_alone = RenderSession(sid=1, poses=list(t_b))
    exclusive.run([b_alone])
    assert b_alone.done
    assert b.stats.hole_fractions == b_alone.stats.hole_fractions
    for fa, fb in zip(b.frames, b_alone.frames):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_fused_serving_tick_zero_host_syncs(fused_setup):
    """The zero-host-sync contract survives the fused path: a steady-state
    fused tick (no admissions => no prime dispatch, recurrence threaded
    device-to-device) runs under ``jax.transfer_guard('disallow')``."""
    r, _, cfg_fused = fused_setup
    serve = RenderServeEngine(r.model, r.params, config=cfg_fused)
    trajs = _trajs(2, 6, step_deg=4.0)
    serve.submit([RenderSession(sid=i, poses=list(t))
                  for i, t in enumerate(trajs)])
    assert serve.step()  # warm-up: admission + prime + compile
    jax.block_until_ready(serve._last_result.frames)
    with jax.transfer_guard("disallow"):
        assert serve.step()  # steady state: pure dispatch
        jax.block_until_ready(serve._last_result.frames)
    while serve.step():
        pass
    serve.finalize()
    assert serve._pending == []
    assert all(slot is None for slot in serve.slots)  # fully drained


def test_delivery_latencies_run_from_the_previous_delivery():
    """A frame waits from its session's previous delivery (its arrival,
    for the first window) to its own; frames delivered together share it,
    and undelivered frames have none."""
    sess = RenderSession(sid=0, poses=[jnp.eye(4)] * 5)
    sess.submitted_s = 10.0
    sess.delivered_s = [12.0, 12.0, 15.5, 15.5, None]
    assert delivery_latencies(sess) == [2.0, 2.0, 3.5, 3.5]


def test_run_reports_delivery_latencies_and_compiles(small_model, cam):
    """run()'s per-session p50/p95 come from the delivery stamps
    finalize() writes, and ``compiles`` counts the engine programs' new
    jit-cache entries in that run: none when a second run reuses the
    shapes."""
    model, params = small_model
    serve = RenderServeEngine(model, params,
                              config=_cfg(cam, num_slots=2, window=2))
    first = [RenderSession(sid=i, poses=list(t))
             for i, t in enumerate(_trajs(2, 3))]
    metrics = serve.run(first)
    assert metrics["compiles"] >= 1
    for sess in first:
        assert all(t is not None for t in sess.delivered_s)
        lat = delivery_latencies(sess)
        assert len(lat) == 3 and min(lat) > 0
        # two windows: the first from arrival, the second from the first
        assert lat[0] == lat[1] == sess.delivered_s[0] - sess.submitted_s
        m = metrics["per_session"][sess.sid]
        assert m["p50_latency_s"] == pytest.approx(np.percentile(lat, 50))
        assert m["p95_latency_s"] == pytest.approx(np.percentile(lat, 95))
    again = serve.run([RenderSession(sid=i, poses=list(t))
                       for i, t in enumerate(_trajs(2, 3))])
    assert again["compiles"] == 0
