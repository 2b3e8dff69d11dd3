"""Multi-session SpaRW render-serving engine (continuous batching of warp
windows).

The LM :class:`~repro.serve.engine.ServeEngine` admits N token streams into
fixed decode slots and runs ONE batched decode step per tick; this module is
its rendering twin. A *session* is one client's camera trajectory (a VR
viewer); the engine admits sessions into fixed **slots**, aligns their warp
**windows** into one device batch, and drives a single
:meth:`~repro.core.engine.DeviceSparwEngine.render_windows` call per
**tick**:

=====================  =====================================
ServeEngine (LM)       RenderServeEngine (SpaRW)
=====================  =====================================
request (prompt)       session (pose trajectory)
decode slot            session slot
prefix KV cache        per-session reference frame
one decode step/tick   one batched warp window/tick
prefill on admit       reference bootstrap on admit
slot reuse on finish   slot reuse on trajectory end
=====================  =====================================

Contracts inherited from the device engine:

* **Zero host syncs per tick** — :meth:`RenderServeEngine.step` only
  dispatches; frames and hole statistics are read back in
  :meth:`RenderServeEngine.finalize`, after every tick has been issued
  (transfer-guard tested).
* **Bit-parity with single-session runs** — a tick stages every slot's
  window into the engine's **flat ray-batch core**
  (:mod:`repro.core.raybatch`): all sessions' reference rays and
  compacted hole samples fuse into single cross-session NeRF calls, and
  an exclusive :class:`~repro.core.engine.DeviceSparwEngine` run is the
  same flat program at S=1 — so every client receives exactly the frames
  its exclusive run would have produced (per-session overflow→dense
  isolation included).
* **One compile for the engine lifetime** — slots make the batch shape
  ``[num_slots, window]`` static; ragged trajectories (sessions joining or
  leaving mid-run) are handled by pose padding + host-side masking, never
  by reshaping the device program.
* **Session sharding** — with ``config.shard`` the flat batch's session
  axis is laid over a device mesh (``num_slots`` divisible by
  ``num_devices``; sessions pinned whole, scatters device-local).
* **Fused streaming serving** — with ``config.fused_tick`` (streaming
  backend) each tick is the single-sweep unified MVoxel pipeline of
  :meth:`~repro.core.engine.DeviceSparwEngine.render_windows_streaming`
  instead of the staged per-chunk path: the engine threads a
  ``[num_slots, H, W]`` cross-tick reference recurrence from dispatch to
  dispatch (tick t co-renders tick t+1's references inside its sweep),
  and admission ticks prime newly admitted slots' rows with ONE batched
  masked render (``prime_reference_select``) — so a steady-state serving
  tick streams the halo table once, and a reused slot can never warp the
  previous occupant's reference.

Per-session reference poses are extrapolated with
:class:`~repro.core.schedule.RefPoseExtrapolator` — the streamed form of
the offtraj schedule, bit-identical to the batch planner.

**Multi-scene serving** (``scene_loader=...``) keys sessions on
``(scene, session)``: each slot's occupant may view a *different* scene,
and the engine pages per-scene MVoxel tables through a device-resident
LRU (:class:`~repro.core.scene_cache.SceneCache`) with
``RenderConfig.scene_cache_bytes`` as the byte budget. The resident set
is a stacked ``[K, ...]`` pair of device arrays (``K = num_slots``
pages); admission of a cached scene uploads nothing, a miss uploads
exactly one dense table (its halo re-layout is built on device) into the
LRU-evicted page. Ticks stay ONE compiled program across scene-set
churn: the stacked shapes are static in ``K``, and the slot→page map
rides in as a traced ``scene_of_seg`` array (re-staged, like the
win_lens/caps signature, only when slot composition changes — a
steady-state mixed-scene tick is still transfer-free). Live slots pin
their scene's page, so an occupant's table can never be stolen
mid-trajectory.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.jitprobe import JitCacheProbe
from repro.core import schedule, streaming
from repro.core.config import (
    _UNSET,
    HoleCapController,
    RenderConfig,
    RenderRequest,
    RenderStats,
    legacy_config,
)
from repro.core.engine import DeviceSparwEngine
from repro.core.scene_cache import SceneCache
from repro.kernels import streaming_pipeline
from repro.nerf import rays
from repro.serve.policies import SchedulingPolicy, resolve_policy


@dataclass
class RenderSession:
    """One client trajectory moving through the serving engine.

    ``window``/``hole_cap`` are per-session overrides of the engine config
    (both bounded by the engine's static capacity — validated at submit);
    ``priority``/``deadline_ms`` feed the admission policy. ``scene``
    names which scene this client views (None = the engine's default
    params; non-None requires a multi-scene engine). ``arrival`` and
    ``submitted_s`` are stamped by :meth:`RenderServeEngine.submit`,
    ``admitted_s`` when the session takes a slot, and ``delivered_s[f]``
    when frame ``f`` reaches the host in :meth:`RenderServeEngine.finalize`
    (:func:`delivery_latencies` turns them into per-frame latencies);
    ``shed=True`` marks a session the policy dropped from the queue (done
    without frames).
    """

    sid: int
    poses: List[jnp.ndarray]  # the trajectory (absorbed window by window)
    frames: List[Optional[jnp.ndarray]] = field(default_factory=list)
    stats: RenderStats = field(default_factory=RenderStats)
    delivered_s: List[Optional[float]] = field(default_factory=list)
    done: bool = False
    window: Optional[int] = None      # per-session warp window override
    hole_cap: Optional[int] = None    # per-session sparse-capacity override
    pool_bucket: Optional[int] = None  # fixed pool-bucket override (pow2)
    priority: int = 0
    deadline_ms: Optional[float] = None
    scene: Optional[str] = None       # (scene, session) serving key
    arrival: int = -1                 # submission order (policy tie-break)
    submitted_s: Optional[float] = None
    admitted_s: Optional[float] = None
    shed: bool = False

    def __post_init__(self) -> None:
        if not self.poses:
            raise ValueError(f"session {self.sid}: empty trajectory")
        self.frames = [None] * len(self.poses)
        self.delivered_s = [None] * len(self.poses)

    @classmethod
    def from_request(cls, request: RenderRequest, sid: int) -> "RenderSession":
        """Build the engine-side session for a declarative request."""
        return cls(sid=request.sid if request.sid is not None else sid,
                   poses=list(request.poses), window=request.window,
                   hole_cap=request.hole_cap,
                   pool_bucket=request.pool_bucket,
                   priority=request.priority,
                   deadline_ms=request.deadline_ms,
                   scene=request.scene)


def delivery_latencies(sess: RenderSession) -> List[float]:
    """Per delivered frame: the seconds from the session's previous
    delivery (its arrival, for the first window) to the delivery that
    brought the frame to the host. Frames delivered together share one
    latency."""
    out: List[float] = []
    prev = sess.submitted_s
    for stamp, group in itertools.groupby(
            t for t in sess.delivered_s if t is not None):
        out += [stamp - prev] * len(list(group))
        prev = stamp
    return out


@dataclass
class _Slot:
    """Engine-side state of an occupied slot."""

    session: RenderSession
    window: int                       # effective warp window for the session
    cap: int                          # effective hole capacity
    cursor: int = 0  # next un-rendered pose index
    extrapolator: Optional[schedule.RefPoseExtrapolator] = None
    # per-session pool-bucket controllers (fresh at admit — a session's
    # bucket ladder walks exactly like its exclusive run's)
    ctl: Optional[HoleCapController] = None
    ctl_c: Optional[HoleCapController] = None
    # fused-tick recurrence: pose of the reference currently held in this
    # slot's row of the engine's cross-tick reference arrays — set by
    # prime-on-admit, then advanced every tick by the fused sweep's
    # co-render (the next window's extrapolated pose)
    ref_pose: Optional[jnp.ndarray] = None
    # multi-scene: the occupant's scene key and its device page — the key
    # pins the page in the SceneCache while this slot is occupied
    scene_key: Optional[str] = None
    page: int = 0


class RenderServeEngine:
    """Fixed-slot continuous batching of SpaRW warp windows.

    Construct with ``config=RenderConfig(...)`` (the legacy
    ``(cam, num_slots=..., window=..., ...)`` kwargs keep working behind a
    ``DeprecationWarning``). ``config.num_slots`` concurrent sessions
    render per tick; further sessions queue and take over slots as earlier
    trajectories finish (slot reuse, exactly like the LM engine's decode
    slots), with the pluggable ``policy`` deciding which queued session is
    admitted into a drained slot (:mod:`repro.serve.policies` — FIFO keeps
    the historical bit-exact behavior).

    Sessions may override ``window`` (≤ ``config.window``) and ``hole_cap``
    (≤ the engine's static capacity) per request; ragged windows batch into
    the single compiled device program via the per-session
    ``win_lens``/``caps`` inputs of
    :meth:`~repro.core.engine.DeviceSparwEngine.render_windows`. The
    staged device copies of those arrays are rebuilt only when slot
    composition changes (admit/drain), so a steady-state tick stays
    transfer-free.
    """

    _LEGACY_DEFAULTS = dict(num_slots=4, window=4, phi_deg=None,
                            hole_cap=None, ray_chunk=RenderConfig.ray_chunk)

    def __init__(self, model, params: dict, cam: Optional[rays.Camera] = None,
                 num_slots=_UNSET, window=_UNSET, phi_deg=_UNSET,
                 hole_cap=_UNSET, ray_chunk=_UNSET, *,
                 config: Optional[RenderConfig] = None,
                 policy: Union[None, str, SchedulingPolicy] = None,
                 scene_loader: Optional[Callable[[str], object]] = None):
        config = legacy_config(
            "RenderServeEngine", cam, config, self._LEGACY_DEFAULTS,
            dict(num_slots=num_slots, window=window, phi_deg=phi_deg,
                 hole_cap=hole_cap, ray_chunk=ray_chunk))
        self.config = config
        self.policy = resolve_policy(policy)
        self.num_slots = config.num_slots
        self.window = config.window
        self.engine = DeviceSparwEngine(model, params, config=config)
        self.slots: List[Optional[_Slot]] = [None] * self.num_slots
        self.queue: List[RenderSession] = []
        self.num_ticks = 0
        self._num_submitted = 0  # arrival stamp for policy tie-breaking
        self._num_shed = 0       # sessions the policy dropped from the queue
        # per-tick telemetry (lifetime logs; run() reports per-run slices)
        self._queue_depth_log: List[int] = []
        self._occupancy_log: List[int] = []
        # --- multi-scene paging (scene_loader) ----------------------------
        # scene name -> device page index, LRU under the byte budget; the
        # stacked [K, ...] arrays ARE the page storage (K = num_slots)
        self.scene_loader = scene_loader
        self.multi_scene = scene_loader is not None
        if self.multi_scene:
            if not self.engine._seg_aware:
                raise ValueError(
                    "multi-scene serving needs the segment-aware streaming "
                    "backend (backend='streaming' with a grid model): the "
                    "scene->segment map rides the flat batch's seg axis")
            base = dict(self.engine.params)
            self._default_table = base.pop("table")
            self._default_mv = base.pop("mv_table")
            self._base_params = base  # decoder etc. — shared across scenes
            k = self.num_slots
            self._table_stack = jnp.zeros(
                (k,) + self._default_table.shape, self._default_table.dtype)
            self._mv_stack = jnp.zeros(
                (k,) + self._default_mv.shape, self._default_mv.dtype)
            self._free_pages = list(range(k))[::-1]  # pop() yields page 0 first
            self.scene_cache = SceneCache(
                budget_bytes=config.scene_cache_bytes, max_entries=k)
            self._num_uploads = 0
            self._uploaded_bytes = 0
            # staged slot->page map (re-uploaded only when it changes)
            self._scene_sig: Optional[Tuple[int, ...]] = None
            self._scene_of_seg = jnp.zeros((k,), jnp.int32)
        # idle slots render a degenerate self-warp (ref == tgt ⇒ zero holes,
        # can never trigger the dense fallback); built once so a tick never
        # transfers a fresh constant to the device
        self._idle_pose = jnp.eye(4)
        # compile the per-slot reference extrapolation now — a steady-state
        # tick is then pure dispatch (transfer-guard tested)
        schedule.extrapolate_pose_jit(
            self._idle_pose, self._idle_pose,
            jnp.asarray(self.window / 2.0, jnp.float32))
        # per-slot (window, cap) signature + its staged device arrays; the
        # arrays are rebuilt (one host→device transfer) only when admission
        # or draining changes the signature — never on a steady-state tick
        self._slot_sig: Optional[Tuple[Tuple[int, int, int, int], ...]] = None
        self._win_lens: Optional[jnp.ndarray] = None
        self._caps: Optional[jnp.ndarray] = None
        # per-session effective pool capacities + the tick's shared static
        # buckets (max over slots — a session still overflows at its OWN
        # controller's budget, carried by the traced pool-cap arrays)
        self._pool_caps: Optional[jnp.ndarray] = None
        self._pool_caps_c: Optional[jnp.ndarray] = None
        self._tick_bucket = 0
        self._tick_bucket_c = 0
        # deferred host readback: (assignments, device result, buckets) per
        # tick, where assignments[s] = (session, [frame indices], ctl,
        # ctl_c) or None
        self._pending: List[tuple] = []
        self._last_result = None
        # per finalized tick: pool bucket/occupancy telemetry for metrics
        self._pool_log: List[dict] = []
        # --- fused streaming serving (RenderConfig.fused_tick) ------------
        # cross-tick reference recurrence: row s of _rgb_ref/_dep_ref holds
        # the reference frame the NEXT tick warps for slot s — co-rendered
        # by the previous tick's fused MVoxel sweep, or freshly primed on
        # the slot's admission tick. Device arrays threaded dispatch-to-
        # dispatch, never read on the host (the zero-host-sync contract
        # covers fused steady-state ticks too).
        self.fused = self.engine.fused_tick
        self._rgb_ref: Optional[jnp.ndarray] = None
        self._dep_ref: Optional[jnp.ndarray] = None
        self._num_admission_ticks = 0  # ticks that ran a prime dispatch
        # fused ticks' RIT counters over every finalized tick (engine
        # lifetime): [stage (hole, ref), (spilled, gathered)] samples, then
        # (pad columns, columns) of the ragged sweeps, then (padding rows,
        # rows) the decoder ran on
        self._rit_counts = np.zeros((4, 2), np.int64)

    # ------------------------------------------------------------------
    def _effective(self, sess: RenderSession) -> Tuple[int, int]:
        """Validate and resolve a session's (window, hole_cap) overrides
        against the engine's static capacities."""
        win = sess.window if sess.window is not None else self.window
        if not 1 <= win <= self.window:
            raise ValueError(
                f"session {sess.sid}: window override {win} outside "
                f"[1, {self.window}] (the engine's compiled batch shape)")
        cap = sess.hole_cap if sess.hole_cap is not None else self.engine.hole_cap
        if not 1 <= cap <= self.engine.hole_cap:
            raise ValueError(
                f"session {sess.sid}: hole_cap override {cap} outside "
                f"[1, {self.engine.hole_cap}] (the engine's static "
                f"compaction capacity)")
        if sess.pool_bucket is not None:
            if not self.engine.pool_holes:
                raise ValueError(
                    f"session {sess.sid}: pool_bucket override set but "
                    f"the engine has pool_holes disabled")
            if sess.pool_bucket > self.engine.pool_ctl.max_bucket:
                raise ValueError(
                    f"session {sess.sid}: pool_bucket override "
                    f"{sess.pool_bucket} exceeds the engine's worst-case "
                    f"bucket {self.engine.pool_ctl.max_bucket}")
        return win, cap

    def _live_sids(self) -> set:
        """sids the engine currently owns: queued or occupying a slot
        (completed sessions release their sid for reuse)."""
        return ({s.sid for s in self.queue}
                | {slot.session.sid for slot in self.slots
                   if slot is not None})

    # ------------------------------------------------------------------
    # multi-scene paging
    # ------------------------------------------------------------------
    def _pinned_scenes(self) -> set:
        """Scene keys whose pages live slots hold — never evictable."""
        return {slot.scene_key for slot in self.slots if slot is not None}

    def _page_of(self, skey: Optional[str], pinned: set) -> int:
        """Resolve ``skey`` to its device page, paging it in on a miss.

        Hit: the scene is already resident — NOTHING is uploaded, the
        admission costs one dict lookup. Miss: the LRU cold (non-pinned)
        scene's page is recycled and exactly one dense table is uploaded
        into it (the halo re-layout is built on device from that upload);
        byte-budget pressure (``scene_cache_bytes``) may free further
        cold pages at the same point.
        """
        page = self.scene_cache.get(skey)
        if page is not None:
            return page
        if not self._free_pages:
            # claim a page before building: insert a placeholder so the
            # cache's own LRU/pin logic picks the victim, then recycle
            # the victim's page for this scene
            for _k, freed in self.scene_cache.put(skey, -1, 0, pinned=pinned):
                if freed >= 0:
                    self._free_pages.append(freed)
            if not self._free_pages:
                raise RuntimeError(
                    "scene cache exhausted: every page is pinned by a live "
                    "slot (more distinct scenes in flight than num_slots "
                    "pages — should be unreachable, slots == pages)")
        page = self._free_pages.pop()
        with jax.profiler.TraceAnnotation("serve.upload"):
            if skey is None:
                table, mv = self._default_table, self._default_mv
            else:
                loaded = self.scene_loader(skey)
                table = (loaded["table"] if isinstance(loaded, dict)
                         else loaded)
                table = jnp.asarray(table, self._default_table.dtype)
                if table.shape != self._default_table.shape:
                    raise ValueError(
                        f"scene {skey!r}: table shape {table.shape} differs "
                        f"from the engine's compiled page shape "
                        f"{self._default_table.shape} (all scenes share one "
                        f"grid geometry)")
                mv = streaming.build_mvoxel_table(
                    table, self.engine.model.streaming_cfg)
            self._table_stack = self._table_stack.at[page].set(table)
            self._mv_stack = self._mv_stack.at[page].set(mv)
        nbytes = int(table.nbytes) + int(mv.nbytes)
        self._num_uploads += 1
        self._uploaded_bytes += nbytes
        for _k, freed in self.scene_cache.put(skey, page, nbytes,
                                              pinned=pinned):
            if freed >= 0:
                self._free_pages.append(freed)
        return page

    def _stage_scene_map(self) -> None:
        """Refresh the staged slot→page device array iff the mapping
        changed (admit/drain/repage), then point the device engine at the
        current stacked params. A steady-state mixed-scene tick re-stages
        nothing — the scene_of_seg transfer happens only on composition
        changes, exactly like the win_lens/caps signature."""
        sig = tuple(slot.page if slot is not None else 0
                    for slot in self.slots)
        if sig != self._scene_sig:
            self._scene_sig = sig
            self._scene_of_seg = jnp.asarray(sig, jnp.int32)
        # dict rebuild is host-only (the arrays are already device-resident);
        # the stacked shapes are static, so this is ONE compile for the
        # engine lifetime no matter which scenes rotate through the pages
        self.engine.params = dict(
            self._base_params, table=self._table_stack,
            mv_table=self._mv_stack, scene_of_seg=self._scene_of_seg)

    def submit(self, sessions: List[RenderSession]) -> None:
        """Queue sessions for admission. The WHOLE batch is validated
        before any engine or session state changes: a rejected batch
        leaves the engine and every session in it exactly as submitted
        found them (no arrival stamps consumed), so the caller can fix
        the offending session and resubmit the same objects. Duplicate
        sids — within the batch or against a live (queued or in-slot)
        session — are rejected: per-session metrics are keyed on sid, and
        two live sessions sharing one would silently collapse into a
        single metrics entry."""
        live = self._live_sids()
        batch_sids = set()
        for sess in sessions:
            self._effective(sess)  # fail fast on impossible overrides
            if sess.scene is not None and not self.multi_scene:
                raise ValueError(
                    f"session {sess.sid}: scene={sess.scene!r} but the "
                    f"engine has no scene_loader (construct with "
                    f"scene_loader=... for multi-scene serving)")
            if sess.sid in live or sess.sid in batch_sids:
                raise ValueError(
                    f"session sid {sess.sid} duplicates a live session "
                    f"(sids must be unique among queued/in-flight sessions"
                    f" — per-session metrics are keyed on sid)")
            batch_sids.add(sess.sid)
        now = time.time()
        for sess in sessions:
            sess.arrival = self._num_submitted
            self._num_submitted += 1
            if sess.submitted_s is None:
                sess.submitted_s = now
        self.queue.extend(sessions)

    def _admit(self) -> List[int]:
        """Fill free slots from the queue (policy choice); returns the
        indices of the slots filled THIS tick. In fused mode the new
        slot's first reference pose is computed here (the extrapolator
        absorbs the first window exactly when the staged path would) —
        the admission tick primes it into the recurrence before the
        fused sweep warps it."""
        now = time.time()
        shed_fn = getattr(self.policy, "shed", None)
        if shed_fn is not None and self.queue:
            # overload shedding: drop queued sessions the policy declares
            # unservable (e.g. deadline already blown) BEFORE they take a
            # slot — the engine degrades by serving fewer sessions well,
            # not every session late
            for i in sorted(shed_fn(self.queue, now), reverse=True):
                sess = self.queue.pop(i)
                sess.shed = True
                sess.done = True
                self._num_shed += 1
        newly: List[int] = []
        for s in range(self.num_slots):
            if self.slots[s] is None and self.queue:
                sess = self.queue.pop(self.policy.select(self.queue, now))
                sess.admitted_s = now
                win, cap = self._effective(sess)
                cfg = self.engine.config
                ctl_kw = dict(worst=win * cap,
                              min_bucket=self.engine.pool_min_bucket,
                              safety=cfg.pool_safety,
                              alpha=cfg.pool_ewma_alpha,
                              fixed=(sess.pool_bucket
                                     if sess.pool_bucket is not None
                                     else cfg.pool_bucket))
                slot = _Slot(
                    session=sess, window=win, cap=cap,
                    extrapolator=schedule.RefPoseExtrapolator(window=win),
                    ctl=HoleCapController(**ctl_kw),
                    ctl_c=HoleCapController(**ctl_kw))
                if self.multi_scene:
                    # page the session's scene in now (upload-on-miss);
                    # already-occupied slots pin their pages so admission
                    # can never steal a live scene
                    slot.scene_key = sess.scene
                    slot.page = self._page_of(sess.scene,
                                              self._pinned_scenes())
                if self.fused:
                    slot.ref_pose = slot.extrapolator.next_reference(
                        sess.poses[:win])
                self.slots[s] = slot
                newly.append(s)
        return newly

    def _prime_admitted(self, newly: List[int]) -> None:
        """Prime the recurrence rows of slots admitted this tick: ONE
        batched staged reference dispatch over the full ``[num_slots]``
        pose batch (new rows get their first window's reference pose,
        everyone else the idle pose — their outputs are discarded by the
        row select), then a bitwise masked substitute
        (:meth:`~repro.core.engine.DeviceSparwEngine.prime_reference_select`).
        Runs only on admission ticks — which already re-stage host-side
        slot masks — so the steady-state zero-host-sync contract is
        untouched, and the static dispatch shape means one prime compile
        per engine lifetime.

        Slot-reuse leak-proofing: a reused slot's row is either fully
        overwritten here (mask True ⇒ ``jnp.where`` never reads the old
        row's lanes into the output) or, while the slot sits idle, holds
        a self-consistent idle-pose render (the drain tick co-renders the
        idle reference into the row — see :meth:`step`), whose self-warp
        has zero holes. The previous occupant's radiance can never reach
        a later session's frames."""
        first = self._rgb_ref is None
        if not newly and not first:
            return
        engine = self.engine
        if first:
            # bootstrap: prime EVERY row (idle rows at the idle pose — the
            # self-consistent idle recurrence) over a zero recurrence; the
            # admitted rows' output is bitwise identical to any later
            # admission's because the select path is the same program
            h, w = engine.cam.height, engine.cam.width
            self._rgb_ref = jnp.zeros((self.num_slots, h, w, 3))
            self._dep_ref = jnp.zeros((self.num_slots, h, w))
            mask = [True] * self.num_slots
        else:
            mask = [s in newly for s in range(self.num_slots)]
        poses = [self.slots[s].ref_pose
                 if mask[s] and self.slots[s] is not None
                 else self._idle_pose for s in range(self.num_slots)]
        self._rgb_ref, self._dep_ref = engine.prime_reference_select(
            jnp.stack(poses), jnp.asarray(mask), self._rgb_ref,
            self._dep_ref)
        self._num_admission_ticks += 1

    def _stage_slot_masks(self) -> None:
        """Refresh the staged per-slot win_lens/caps/pool-caps device
        arrays iff the slot signature changed — composition (admit/drain)
        or a pool-controller ladder step (idle slots take the engine
        defaults and the minimum pool bucket: their self-warp has zero
        holes, so any capacity is unreachable and they never inflate the
        tick's shared bucket)."""
        engine = self.engine
        adaptive = engine.adaptive_sampling
        sig = []
        for slot in self.slots:
            if slot is None:
                bf = engine.pool_min_bucket if engine.pool_holes else 0
                sig.append((self.window, engine.hole_cap, bf,
                            bf if adaptive else 0))
            elif not engine.pool_holes:
                sig.append((slot.window, slot.cap, 0, 0))
            else:
                sig.append((slot.window, slot.cap, slot.ctl.bucket,
                            slot.ctl_c.bucket if adaptive else 0))
        sig = tuple(sig)
        if sig != self._slot_sig:
            self._slot_sig = sig
            self._win_lens = jnp.asarray([e[0] for e in sig], jnp.int32)
            self._caps = jnp.asarray([e[1] for e in sig], jnp.int32)
            self._pool_caps = jnp.asarray([e[2] for e in sig], jnp.int32)
            self._pool_caps_c = jnp.asarray([e[3] for e in sig], jnp.int32)
            self._tick_bucket = max(e[2] for e in sig)
            self._tick_bucket_c = max(e[3] for e in sig)

    def step(self) -> bool:
        """One engine tick: admit queued sessions into free slots (policy
        choice), then ONE batched device call rendering every active
        session's next warp window. Dispatch-only — no device→host transfer
        happens here; call :meth:`finalize` (or :meth:`run`) to materialize
        frames and stats. Returns False when no work remains.

        With ``config.fused_tick`` the device call is the unified
        streaming tick: the sweep warps the references CO-RENDERED by the
        previous tick (held in the engine's recurrence arrays; newly
        admitted slots primed this tick) and co-renders the next tick's
        references — the serving form of the cross-tick pipelining in
        :meth:`~repro.core.engine.DeviceSparwEngine.render_trajectory`.
        A draining slot's last sweep co-renders an IDLE reference into
        its row (ref pose == idle target pose ⇒ the idle self-warp stays
        hole-free), so a freed slot's recurrence is self-consistent until
        prime-on-admit overwrites it for the next occupant."""
        with jax.profiler.TraceAnnotation("serve.step"):
            return self._step()

    def _step(self) -> bool:
        with jax.profiler.TraceAnnotation("serve.admit"):
            newly = self._admit()
            occupied = sum(s is not None for s in self.slots)
            if occupied == 0:
                return False
            # post-admission backlog + occupancy telemetry (per-tick; run()
            # reports per-run slices of these lifetime logs)
            self._queue_depth_log.append(len(self.queue))
            self._occupancy_log.append(occupied)
        with jax.profiler.TraceAnnotation("serve.stage"):
            self._stage_slot_masks()
            if self.multi_scene:
                self._stage_scene_map()
        if self.fused:
            with jax.profiler.TraceAnnotation("serve.prime"):
                self._prime_admitted(newly)

        with jax.profiler.TraceAnnotation("serve.stage"):
            ref_poses, tgt_poses, next_refs, assignments = self._stage_poses()
            ref_poses = jnp.stack(ref_poses)
            tgt_poses = jnp.stack([jnp.stack(t) for t in tgt_poses])
            if self.fused:
                next_refs = jnp.stack(next_refs)
        with jax.profiler.TraceAnnotation("serve.dispatch"):
            if self.fused:
                result = self.engine.render_windows_streaming(
                    self._rgb_ref, self._dep_ref, ref_poses, tgt_poses,
                    next_refs, self._win_lens, self._caps,
                    pool_caps=self._pool_caps, bucket=self._tick_bucket)
                # thread the co-rendered references to the next dispatch —
                # device-resident, never synced
                self._rgb_ref = result.next_rgb_ref
                self._dep_ref = result.next_dep_ref
            else:
                result = self.engine.render_windows(
                    ref_poses, tgt_poses, self._win_lens, self._caps,
                    pool_caps=self._pool_caps,
                    pool_caps_coarse=self._pool_caps_c,
                    bucket=self._tick_bucket,
                    bucket_coarse=self._tick_bucket_c)
        self._pending.append(
            (assignments, result, (self._tick_bucket, self._tick_bucket_c)))
        self._last_result = result
        self.num_ticks += 1
        return True

    def _stage_poses(self) -> Tuple[list, list, list, list]:
        """Each slot's reference pose, padded target window and next
        reference pose (the idle pose for empty slots), and the tick's
        assignments; advances every occupied slot's cursor and frees the
        slots whose trajectory this tick ends."""
        ref_poses, tgt_poses, next_refs, assignments = [], [], [], []
        for s in range(self.num_slots):
            slot = self.slots[s]
            if slot is None:
                ref_poses.append(self._idle_pose)
                tgt_poses.append([self._idle_pose] * self.window)
                next_refs.append(self._idle_pose)
                assignments.append(None)
                continue
            sess = slot.session
            idxs = list(range(slot.cursor,
                              min(slot.cursor + slot.window, len(sess.poses))))
            win = [sess.poses[i] for i in idxs]
            if self.fused:
                # the window's reference pose was already extrapolated —
                # at admit (primed) or by the previous tick's co-render
                ref_poses.append(slot.ref_pose)
            else:
                ref_poses.append(slot.extrapolator.next_reference(win))
            # pad short windows (per-session override and/or trajectory
            # tail) with the last real pose up to the engine's static batch
            # width — padded frames are rendered and discarded on the host,
            # and the win_lens mask keeps them out of the overflow decision
            tgt_poses.append(win + [win[-1]] * (self.window - len(win)))
            assignments.append((sess, idxs, slot.ctl, slot.ctl_c))
            sess.stats.reference_renders += 1
            slot.cursor += len(idxs)
            if slot.cursor >= len(sess.poses):
                # slot reuse: free for the next admit. The fused sweep
                # co-renders the idle reference into the freed row so the
                # idle self-warp (and any later occupant, pre-prime) can
                # never see this session's radiance.
                next_refs.append(self._idle_pose)
                self.slots[s] = None
            elif self.fused:
                nxt = range(slot.cursor,
                            min(slot.cursor + slot.window, len(sess.poses)))
                slot.ref_pose = slot.extrapolator.next_reference(
                    [sess.poses[i] for i in nxt])
                next_refs.append(slot.ref_pose)
            else:
                next_refs.append(self._idle_pose)
        return ref_poses, tgt_poses, next_refs, assignments

    # ------------------------------------------------------------------
    def finalize(self, keep: int = 0) -> None:
        """Materialize pending ticks' frames and hole statistics on the
        host (the only device→host transfers in the engine). ``keep``
        leaves that many of the *newest* ticks pending — :meth:`run` uses
        it to drain completed ticks while one tick is still in flight."""
        hw = self.engine.cam.height * self.engine.cam.width
        pool = self.engine.pool_holes
        adaptive = self.engine.adaptive_sampling
        split = max(len(self._pending) - keep, 0)
        done, self._pending = self._pending[:split], self._pending[split:]
        for assignments, res, (bf, bc) in done:
            with jax.profiler.TraceAnnotation("serve.readback"):
                rit = np.asarray(res.rit_counts) if self.fused else None
                counts = np.asarray(res.hole_counts)
                fine = np.asarray(res.fine_counts)
                overflowed = np.asarray(res.overflowed)
            delivered = time.time()
            with jax.profiler.TraceAnnotation("serve.bookkeep"):
                if rit is not None:
                    self._rit_counts += rit
                tick_holes = tick_fine = active = 0
                for s, assign in enumerate(assignments):
                    if assign is None:
                        continue
                    sess, idxs, ctl, ctl_c = assign
                    ovf = bool(overflowed[s])
                    for j, f in enumerate(idxs):
                        sess.frames[f] = res.frames[s, j]
                        sess.delivered_s[f] = delivered
                        sess.stats.record_frame(int(counts[s, j]), ovf, hw)
                    if sess.frames.count(None) == 0:
                        sess.done = True
                    win_total = int(counts[s, :len(idxs)].sum())
                    fine_total = int(fine[s, :len(idxs)].sum())
                    tick_holes += win_total
                    tick_fine += fine_total
                    active += 1
                    # feed the session's pool controllers — the readback
                    # runs a tick behind dispatch, so observations land two
                    # dispatches after the window they describe (the
                    # cadence the exclusive engine's render_trajectory
                    # mirrors)
                    if pool and ctl is not None:
                        ctl.observe(fine_total)
                        if adaptive:
                            ctl_c.observe(win_total - fine_total)
                if pool:
                    self._pool_log.append(dict(
                        bucket=bf, bucket_coarse=bc, hole_total=tick_holes,
                        fine_total=tick_fine, active_slots=active))

    def run(self, sessions: List[RenderSession], max_ticks: int = 10_000
            ) -> Dict[str, object]:
        """Serve ``sessions`` to completion; returns aggregate metrics.

        The loop runs ONE tick ahead of the device: tick t+1 is dispatched
        before blocking on tick t's completion, so host orchestration
        (admission, pose staging) overlaps device compute instead of
        serializing against it — the continuous-batching analogue of the
        single-session engine's dispatch-then-read-back discipline.
        A frame's latency runs from its session's previous delivery (its
        arrival, for the first window) to the delivery that brought it to
        the host (:func:`delivery_latencies`), and completed ticks are
        drained as the loop advances so device memory stays bounded at the
        pipeline depth regardless of trajectory length. ``compiles`` counts
        the new jit-cache entries of the engine's programs in this run
        (:class:`~repro.analysis.jitprobe.JitCacheProbe`). The
        zero-host-sync contract applies to bare :meth:`step`, not
        :meth:`run`.
        """
        self.submit(sessions)
        start_ticks = self.num_ticks  # the engine may be reused across runs
        log_start = len(self._pool_log)
        # THIS run's recompile / admission spend, not engine-lifetime
        # totals: a reused engine keeps its compiled-bucket cache (and its
        # admission count) across runs, so report the deltas
        buckets_start = len(self.engine.pool_buckets_used)
        adm_start = self._num_admission_ticks
        rit_start = self._rit_counts.copy()
        # same per-run-delta convention for queue/occupancy/scene-cache
        qd_start = len(self._queue_depth_log)
        shed_start = self._num_shed
        sc_start = (dict(self.scene_cache.counters(),
                         uploads=self._num_uploads,
                         uploaded_bytes=self._uploaded_bytes)
                    if self.multi_scene else None)
        probe = JitCacheProbe(self.engine)
        t0 = time.time()
        in_flight = None  # the device result of the tick still running
        while self.num_ticks - start_ticks < max_ticks:
            if not self.step():
                break
            if in_flight is not None:
                jax.block_until_ready(in_flight.frames)
                self.finalize(keep=1)  # drain all completed ticks
            in_flight = self._last_result
        if in_flight is not None:
            jax.block_until_ready(in_flight.frames)
        wall_s = time.time() - t0
        self.finalize()
        latencies = {s.sid: delivery_latencies(s) for s in sessions}
        # shed sessions render nothing — they must not inflate throughput
        total_frames = sum(len(s.poses) for s in sessions if not s.shed)
        per_session = {
            s.sid: {
                "frames": len(s.poses),
                "p50_latency_s": float(np.percentile(latencies[s.sid], 50))
                if latencies[s.sid] else float("nan"),
                "p95_latency_s": float(np.percentile(latencies[s.sid], 95))
                if latencies[s.sid] else float("nan"),
                "hole_fraction": s.stats.mean_hole_fraction,
                "scene": s.scene,
                "shed": s.shed,
            } for s in sessions
        }
        # admission-queue + slot-occupancy telemetry, per-run deltas/slices
        depths = self._queue_depth_log[qd_start:]
        occs = self._occupancy_log[qd_start:]
        waits = [s.admitted_s - s.submitted_s for s in sessions
                 if s.admitted_s is not None and s.submitted_s is not None]
        queue_metrics = {
            "depth_mean": float(np.mean(depths)) if depths else 0.0,
            "depth_max": int(max(depths)) if depths else 0,
            "wait_p50_s": float(np.percentile(waits, 50)) if waits else 0.0,
            "wait_p95_s": float(np.percentile(waits, 95)) if waits else 0.0,
            "shed": self._num_shed - shed_start,
        }
        slot_metrics = {
            "num_slots": self.num_slots,
            "occupancy_mean": (float(np.mean(occs)) / self.num_slots
                               if occs else 0.0),
            "active_slot_ticks": int(sum(occs)),
        }
        # scene-cache hit/miss/eviction spend of THIS run (lifetime
        # counters snapshotted at entry — the pool.recompiles convention)
        scene_metrics = None
        if self.multi_scene:
            end = dict(self.scene_cache.counters(),
                       uploads=self._num_uploads,
                       uploaded_bytes=self._uploaded_bytes)
            scene_metrics = {
                k: end[k] - sc_start[k]
                for k in ("hits", "misses", "evictions", "evicted_bytes",
                          "uploads", "uploaded_bytes")}
            looked = scene_metrics["hits"] + scene_metrics["misses"]
            scene_metrics["hit_rate"] = scene_metrics["hits"] / max(looked, 1)
            scene_metrics["resident_bytes"] = end["resident_bytes"]
            scene_metrics["resident_scenes"] = end["entries"]
            scene_metrics["budget_bytes"] = self.config.scene_cache_bytes
        # pooled-capacity telemetry: sparse NeRF samples actually reserved
        # per tick vs the worst-case fixed-cap batch, pool occupancy, and
        # the recompile budget actually spent walking the bucket ladder
        engine = self.engine
        ns = engine.model.cfg.num_samples
        fixed_spt = self.num_slots * self.window * engine.hole_cap * ns
        entries = self._pool_log[log_start:]
        if engine.pool_holes and entries:
            def _spt(e):
                return self.num_slots * (
                    e["bucket"] * ns
                    + e["bucket_coarse"] * (ns // engine.coarse_factor))
            samples_last = _spt(entries[-1])  # steady-state (post-warm-up)
            samples_mean = float(np.mean([_spt(e) for e in entries]))
            pool_slots = sum(
                self.num_slots * (e["bucket"] + e["bucket_coarse"])
                for e in entries)
            util = float(sum(e["hole_total"] for e in entries)
                         / max(pool_slots, 1))
        else:
            samples_last, samples_mean, util = fixed_spt, float(fixed_spt), float("nan")
        pool_metrics = {
            "enabled": engine.pool_holes,
            "adaptive_sampling": engine.adaptive_sampling,
            "samples_per_tick": samples_last,
            "samples_per_tick_mean": samples_mean,
            "samples_per_tick_fixed_cap": fixed_spt,
            "work_reduction_vs_fixed_cap": fixed_spt / max(samples_last, 1),
            "utilization": util,
            "recompiles": len(engine.pool_buckets_used) - buckets_start,
            "ladder_size": engine.pool_ladder_size,
        }
        # per-tick MVoxel-table traffic accounting (streaming backend only:
        # analytic staged-vs-fused sweep counts at this engine's shapes —
        # what the serving tick would move on the staged path vs the
        # unified streaming pipeline)
        memory_metrics = (engine.tick_memory_stats(
            self.num_slots, self.window,
            bucket=self._tick_bucket if self._tick_bucket else None)
            if engine._seg_aware else None)
        if memory_metrics is not None:
            ticks_run = self.num_ticks - start_ticks
            adm_ticks = self._num_admission_ticks - adm_start
            fused = self.fused
            memory_metrics["serving_path"] = "fused" if fused else "staged"
            memory_metrics["admission_ticks"] = adm_ticks
            # steady-state serving tick: ONE dual-RIT sweep on the fused
            # path vs the staged per-chunk re-streams; admission ticks add
            # the prime's staged reference sweeps, amortized over the run
            memory_metrics["serving_table_sweeps_per_tick_steady"] = (
                1.0 if fused
                else memory_metrics["staged_table_sweeps_per_tick"])
            memory_metrics["serving_table_sweeps_per_tick_amortized"] = (
                streaming_pipeline.serving_sweeps_per_tick(
                    ticks_run, adm_ticks,
                    memory_metrics["staged_ref_sweeps"]) if fused
                else memory_metrics["staged_table_sweeps_per_tick"])
        # fused ticks' RIT per stage: the share of live gather samples that
        # spilled past the RIT (0: it is ragged), and the sweep's padding
        rit_metrics = None
        if self.fused:
            rit = self._rit_counts - rit_start
            rit_metrics = {
                stage: {"spilled_samples": int(rit[i, 0]),
                        "samples": int(rit[i, 1]),
                        "overflow_share": float(rit[i, 0] / max(rit[i, 1], 1))}
                for i, stage in enumerate(("hole", "ref"))}
            rit_metrics["pad"] = {
                "pad_columns": int(rit[2, 0]), "columns": int(rit[2, 1]),
                "pad_share": float(rit[2, 0] / max(rit[2, 1], 1))}
            rit_metrics["decode"] = {
                "pad_rows": int(rit[3, 0]), "rows": int(rit[3, 1]),
                "pad_share": float(rit[3, 0] / max(rit[3, 1], 1))}
        return {
            "ticks": self.num_ticks - start_ticks,
            "compiles": probe.recompiles(),
            "wall_s": wall_s,
            "aggregate_fps": total_frames / max(wall_s, 1e-9),
            "total_frames": total_frames,
            "per_session": per_session,
            "complete": all(s.done for s in sessions),
            "policy": self.policy.name,
            "pool": pool_metrics,
            "memory": memory_metrics,
            "rit_overflow": rit_metrics,
            "queue": queue_metrics,
            "slots": slot_metrics,
            "scene_cache": scene_metrics,
            # session-sharding layout (1 = unsharded/single device)
            "devices": (self.engine.mesh.devices.size
                        if self.engine.mesh is not None else 1),
        }
