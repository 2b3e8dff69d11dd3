"""CiceroRenderer — the end-to-end SPARW rendering pipeline (paper Fig. 10).

Two engines drive the same algorithm:

* ``engine="device"`` (default for the off-trajectory schedule) — the
  device-resident path in :mod:`repro.core.engine`: each warp window
  (reference render → batched warp → fixed-capacity sparse render →
  combine) is ONE jitted call with zero host synchronization inside the
  window. This is the architecture the paper's speedups assume.
* ``engine="host"`` — the seed host-side frame loop, kept as the reference
  implementation: per-frame ``np.nonzero`` hole round-trips and
  variable-length ray chunks. Used for parity tests, the TEMP-N baseline
  (inherently serialized) and as the benchmark's "before" measurement.

Also provides the paper's comparison baselines: full NeRF every frame,
DS-2 (render at half res + bilinear upsample), and TEMP-N (warp from the
previously *rendered* frame — serialized, error-accumulating).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import schedule, sparw
from repro.core.config import (  # noqa: F401 (RenderStats re-export)
    _UNSET,
    RenderConfig,
    RenderRequest,
    RenderResult,
    RenderStats,
    legacy_config,
)
from repro.core.engine import DeviceSparwEngine  # noqa: F401 (re-export)
from repro.core.scene_cache import ParamsToken, SceneCache
from repro.nerf import models, rays
from repro.utils import psnr


# The identity-token + LRU machinery generalized into the byte-budgeted
# SceneCache (core/scene_cache.py) for multi-scene serving; the engine
# caches below stay count-bounded specializations of it. ``_ParamsToken``
# keys on object identity and keeps the keyed object alive, so a GC'd
# params dict can never recycle its id() into someone else's engine.
_ParamsToken = ParamsToken


class _EngineLRU(SceneCache):
    """Small least-recently-used cache for compiled engines.

    Long-lived servers render many distinct per-request override configs;
    an unbounded ``dict`` leaks one compiled engine per distinct
    ``(params, config)`` forever. This keeps the ``maxsize`` most recently
    *used* entries (a plain bounded dict evicts by insertion order, which
    throws away the hottest engine under a cyclic access pattern). An
    evicted engine keeps working for anyone holding it — only the cache
    forgets it.
    """

    def __init__(self, maxsize: int = 16):
        super().__init__(max_entries=maxsize)
        self.maxsize = maxsize

    def put(self, key: tuple, value: object) -> None:
        super().put(key, value, nbytes=0)


class CiceroRenderer:
    """Construct with ``config=RenderConfig(...)``; the legacy
    ``(cam, window=..., mode=..., engine=..., ...)`` kwargs keep working
    behind a ``DeprecationWarning``. The compile-relevant knobs live in the
    frozen config (exposed read-only — mutating a renderer mid-life was the
    stale-engine-cache hazard the config keying exists to close); engines
    are cached per ``(params identity, RenderConfig)`` so any knob change
    transparently builds/reuses the right compiled program.
    """

    _LEGACY_DEFAULTS = dict(window=16, phi_deg=None, mode="offtraj",
                            engine="device", hole_cap=None)

    def __init__(self, model: models.NerfModel, params: dict,
                 cam: Optional[rays.Camera] = None,
                 window=_UNSET, phi_deg=_UNSET, mode=_UNSET, engine=_UNSET,
                 hole_cap=_UNSET, *, config: Optional[RenderConfig] = None):
        config = legacy_config(
            "CiceroRenderer", cam, config, self._LEGACY_DEFAULTS,
            dict(window=window, phi_deg=phi_deg, mode=mode, engine=engine,
                 hole_cap=hole_cap))
        self.config = config
        self.model = model
        # streaming backend: hoist the MVoxel halo re-layout out of every
        # render path (host loop, baselines, DS-2) — no-op otherwise
        self.params = model.prepare_streaming(params)
        self.cam = config.camera
        self._render_rays = model.render_rays_jit  # cached once per model
        self._warp = jax.jit(
            lambda rgb, dep, p_ref, p_tgt: sparw.warp_frame(
                rgb, dep, p_ref, p_tgt, self.cam, phi_deg=config.phi_deg))
        # engine caches keyed on the FULL config (hash == compile surface)
        # plus a weakref-safe params identity token — never on a lone knob
        # like num_slots (stale-program hazard) nor on a raw id() (recycled
        # after GC, so two distinct params could alias one engine). LRU:
        # per-request overrides would otherwise grow one compiled engine
        # per distinct (window, hole_cap) pair forever.
        self._device_engines = _EngineLRU()
        self._serve_engines = _EngineLRU()

    # read-only views of the compile-relevant knobs (kwarg-era attributes)
    @property
    def window(self) -> int:
        return self.config.window

    @property
    def phi_deg(self) -> Optional[float]:
        return self.config.phi_deg

    @property
    def mode(self) -> str:
        return self.config.mode

    @property
    def engine(self) -> str:
        return self.config.engine

    @property
    def hole_cap(self) -> Optional[int]:
        return self.config.hole_cap

    def _engine_key(self, config: RenderConfig) -> tuple:
        return (_ParamsToken(self.params), config)

    def device_engine_for(self, config: RenderConfig) -> DeviceSparwEngine:
        """The cached device engine compiled for ``config`` (built on first
        use; one engine per distinct compile surface, LRU-bounded)."""
        key = self._engine_key(config)
        eng = self._device_engines.get(key)
        if eng is None:
            eng = DeviceSparwEngine(self.model, self.params, config=config)
            self._device_engines.put(key, eng)
        return eng

    @property
    def device_engine(self) -> DeviceSparwEngine:
        return self.device_engine_for(self.config)

    # ------------------------------------------------------------------
    def full_frame(self, c2w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return self.model.render_image(self.params, self.cam, c2w)

    def sparse_frame(self, c2w: jnp.ndarray, holes: np.ndarray) -> jnp.ndarray:
        """Host-loop sparse render: capacity = exact hole count, chunked.
        Returns a full [H,W,3] image with non-hole pixels zero."""
        h, w = self.cam.height, self.cam.width
        o, d = rays.generate_rays(self.cam, c2w)
        idx = np.nonzero(holes.reshape(-1))[0]
        out = np.zeros((h * w, 3), np.float32)
        chunk = 1 << 13
        for i in range(0, len(idx), chunk):
            sel = jnp.asarray(idx[i : i + chunk])
            col, _ = self._render_rays(self.params, o[sel], d[sel])
            out[idx[i : i + chunk]] = np.asarray(col)
        return jnp.asarray(out.reshape(h, w, 3))

    # ------------------------------------------------------------------
    def render_trajectory(self, poses: Sequence[jnp.ndarray], *,
                          config: Optional[RenderConfig] = None
                          ) -> Tuple[List[jnp.ndarray], RenderStats]:
        """SPARW rendering of a pose trajectory. Returns (frames, stats).

        Routes through the device-resident engine except for the serialized
        TEMP-N mode (whose reference depends on the previous *rendered*
        frame) or when ``engine="host"`` was requested explicitly.
        ``config`` renders with a variant compile surface (e.g. a request's
        ``window``/``hole_cap`` overrides) through the per-config engine
        cache.
        """
        cfg = config or self.config
        if cfg.engine == "device" and cfg.mode == "offtraj":
            return self.device_engine_for(cfg).render_trajectory(list(poses))
        return self.render_trajectory_host(list(poses), config=cfg)

    def render(self, request: RenderRequest) -> RenderResult:
        """Render one declarative :class:`RenderRequest` (the single-session
        form of the unified API; :mod:`repro.api` wraps this). Folds the
        request's ``window``/``hole_cap`` overrides into the config, renders
        the trajectory, and returns frames + stats + wall-clock timing."""
        import time as _time

        cfg = self.config.apply_request(request)
        t0 = _time.time()
        frames, stats = self.render_trajectory(request.poses, config=cfg)
        jax.block_until_ready(frames)
        return RenderResult(frames=tuple(frames), stats=stats,
                            wall_s=_time.time() - t0, sid=request.sid)

    def serve_engine_for(self, config: RenderConfig):
        """The cached serving engine for ``config`` — keyed on the FULL
        config (slots + window + hole_cap + every other compile knob, plus
        the weakref-safe params token at lookup time), closing both the
        stale-cache hazard of the old per-``num_slots`` keying and the
        recycled-``id()`` aliasing of the old ``(id(params), config)``
        key. LRU-bounded for long-lived servers."""
        from repro.serve.render_engine import RenderServeEngine

        key = self._engine_key(config)
        serve = self._serve_engines.get(key)
        if serve is None:
            serve = RenderServeEngine(self.model, self.params, config=config)
            self._serve_engines.put(key, serve)
        return serve

    def serve(self, requests: Sequence[Union[RenderRequest, Sequence[jnp.ndarray]]],
              policy=None, num_slots: Optional[int] = None
              ) -> Tuple[List[RenderResult], Dict[str, object]]:
        """Serve several :class:`RenderRequest` sessions through ONE batched
        device program per tick (continuous batching of warp windows — see
        :mod:`repro.serve.render_engine`), with a pluggable admission
        ``policy`` (:mod:`repro.serve.policies`; default FIFO, which is
        bit-identical to pre-policy serving).

        Returns (per-request :class:`RenderResult` list, serve metrics).
        Each session's frames bit-match what :meth:`render` would produce
        for it alone (per-session ``window``/``hole_cap`` overrides
        included).
        """
        from repro.serve.render_engine import RenderSession

        if self.config.mode != "offtraj":
            raise ValueError("multi-session serving requires mode='offtraj' "
                             "(TEMP-N is inherently serialized)")
        reqs = [r if isinstance(r, RenderRequest)
                else RenderRequest(poses=tuple(r)) for r in requests]
        slots = num_slots or self.config.num_slots
        serve = self.serve_engine_for(self.config.replace(num_slots=slots))
        from repro.serve.policies import resolve_policy
        serve.policy = resolve_policy(policy)
        sessions = [RenderSession.from_request(req, sid=i)
                    for i, req in enumerate(reqs)]
        metrics = serve.run(sessions)
        # a session's wall-clock: from its arrival to its last delivery
        results = [RenderResult(
            frames=tuple(s.frames), stats=s.stats, sid=s.sid,
            wall_s=max((t for t in s.delivered_s if t is not None),
                       default=s.submitted_s) - s.submitted_s)
            for s in sessions]
        return results, metrics

    def render_trajectories(self, trajectories: List[List[jnp.ndarray]],
                            num_slots: Optional[int] = None
                            ) -> Tuple[List[List[jnp.ndarray]],
                                       List[RenderStats], Dict[str, object]]:
        """Multi-session SPARW over bare pose lists (the pre-request API;
        now a thin wrapper over :meth:`serve` with FIFO admission — the
        output is bit-identical to the historical engine).

        Returns (per-session frame lists, per-session stats, serve
        metrics). Each session's frames bit-match what
        :meth:`render_trajectory` would produce for it alone.
        """
        results, metrics = self.serve(
            [RenderRequest(poses=tuple(t)) for t in trajectories],
            policy="fifo", num_slots=num_slots or len(trajectories))
        return ([list(r.frames) for r in results],
                [r.stats for r in results], metrics)

    def render_trajectory_host(self, poses: List[jnp.ndarray], *,
                               config: Optional[RenderConfig] = None
                               ) -> Tuple[List[jnp.ndarray], RenderStats]:
        """The seed host-side frame loop (one frame at a time, hole mask
        synced to host every frame). Reference implementation + TEMP-N."""
        cfg = config or self.config
        stats = RenderStats()
        plan = schedule.WarpSchedule(cfg.window, cfg.mode).plan(poses)
        frames: List[Optional[jnp.ndarray]] = [None] * len(poses)
        ref_cache: Dict[int, Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = {}

        for rec in plan:
            f = rec["frame"]
            k = rec["window_start"]
            if k not in ref_cache:
                if cfg.mode == "temporal" and rec["ref_frame_idx"] is not None \
                        and frames[rec["ref_frame_idx"]] is not None:
                    # TEMP-N: reuse the previously *rendered* (warped) frame —
                    # depth comes from a render of that pose (paper's TEMP-16
                    # accumulates error exactly this way)
                    ref_pose = poses[rec["ref_frame_idx"]]
                    rgb_ref = frames[rec["ref_frame_idx"]]
                    _, dep_ref = self.full_frame(ref_pose)
                else:
                    ref_pose = rec["ref_pose"]
                    rgb_ref, dep_ref = self.full_frame(ref_pose)
                    stats.reference_renders += 1
                ref_cache = {k: (rgb_ref, dep_ref, ref_pose)}  # keep one window

            rgb_ref, dep_ref, ref_pose = ref_cache[k]
            warped = self._warp(rgb_ref, dep_ref, ref_pose, poses[f])
            holes = np.asarray(warped.holes)
            sparse_rgb = self.sparse_frame(poses[f], holes)
            frame = sparw.combine(warped, sparse_rgb, warped.holes)
            frames[f] = frame

            stats.frames += 1
            stats.total_pixels += holes.size
            stats.sparse_pixels += int(holes.sum())
            stats.warped_pixels += int(holes.size - holes.sum())
            stats.hole_fractions.append(float(holes.mean()))
        return [f for f in frames if f is not None], stats

    # ------------------------------------------------------------------
    def render_baseline(self, poses: List[jnp.ndarray]) -> List[jnp.ndarray]:
        return [self.full_frame(p)[0] for p in poses]

    def render_ds2(self, poses: List[jnp.ndarray]) -> List[jnp.ndarray]:
        """DS-2 baseline: render at half resolution, bilinear-upsample ×2."""
        half = rays.Camera(self.cam.height // 2, self.cam.width // 2,
                           self.cam.focal / 2.0, self.cam.cx / 2.0,
                           self.cam.cy / 2.0)
        out = []
        for p in poses:
            img, _ = self.model.render_image(self.params, half, p)
            up = jax.image.resize(img, (self.cam.height, self.cam.width, 3),
                                  method="bilinear")
            out.append(up)
        return out


def trajectory_psnr(frames: List[jnp.ndarray], gt: List[jnp.ndarray]) -> float:
    vals = [float(psnr(f, g)) for f, g in zip(frames, gt)]
    return float(np.mean(vals))


def orbit_trajectory(n_frames: int, step_deg: float = 1.0, radius: float = 2.6,
                     wobble: float = 0.05, phase_deg: float = 0.0
                     ) -> List[jnp.ndarray]:
    """A smooth camera trajectory (consecutive frames in close proximity —
    the paper's real-time rendering premise, Fig. 7). ``phase_deg`` offsets
    the orbit start so concurrent serving sessions each get a distinct
    viewpoint stream."""
    return [rays.orbit_pose(jnp.deg2rad(phase_deg + i * step_deg),
                            radius=radius, wobble=wobble)
            for i in range(n_frames)]
