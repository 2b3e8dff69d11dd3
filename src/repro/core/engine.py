"""Device-resident SpaRW render engine (paper Fig. 10 as ONE device program).

The seed renderer (`repro.core.pipeline.CiceroRenderer`'s host loop) drives
SPARW from Python: every frame it round-trips the hole mask to the host
(``np.nonzero``), re-slices variable-length ray batches (forcing an XLA
recompile whenever the hole count changes) and never reaches the Pallas
kernels. This module is the device-resident replacement — the architecture
Potamoi/RT-NeRF argue for: keep the whole warp→gather→MLP→composite chain on
the accelerator with no per-frame host synchronization.

Design (the **flat ray-batch execution core**, :mod:`repro.core.raybatch`):

* ``render_windows`` renders S concurrent sessions' warp windows as ONE
  jitted call built from flat cross-session stages instead of a
  per-session pipeline ``vmap``-ed over a leading S axis:

  ① every session's reference rays pack into one ``[S*HW]`` flat batch and
  render through ONE fused NeRF call; ② all ``S×N`` target frames warp in
  one flat scatter pass (:func:`repro.core.sparw.warp_frames_flat`);
  ③ hole compaction emits flat segment offsets
  (:func:`repro.core.sparw.compact_holes_flat`) into a fixed-capacity
  ``[S*N*cap]`` flat hole batch; ④ that batch renders through ONE fused
  sparse NeRF call and segment-scatters back to ``[S, N, H, W, 3]``
  frames. The Pallas kernels (``gather_features_streaming`` →
  ``nerf_mlp``) therefore see large contiguous inputs — one RIT build and
  one kernel launch per stage per tick, not S small vmapped ones.

* ``render_window`` (single session) is the same program at S=1 — an
  exclusive run and a batched run execute identical per-ray code, which is
  what makes the serving engine's bit-parity contract structural.

* Hole handling uses **fixed-capacity compaction**: hole pixel indices are
  compacted (deterministic cumsum scatter, no ``nonzero``) into a static
  ``[hole_cap]`` ray batch per frame. A session whose window overflows the
  capacity takes a dense re-render of its frames in isolation; its
  neighbours keep the sparse-path output bit-for-bit. Per-session
  ``win_lens``/``caps`` are traced inputs, so ragged windows batch into
  the same compiled program.

* **Multi-device session sharding** (``RenderConfig.shard``): the flat
  layout is session-major, so laying a ``NamedSharding`` over the leading
  session axis pins each session's rays, holes and frames to one device —
  no scatter crosses a device boundary. ``shard=None`` (or one device) is
  bit-identical to the unsharded engine.

* With ``NerfModel`` ``backend="streaming"`` the NeRF evaluation runs
  through the Pallas kernels end-to-end; the MVoxel halo table is built
  once per params (``prepare_streaming``) and broadcast across sessions,
  and the flat batch carries per-ray *segment ids* so the fused gather
  drops chunk padding from its ragged RIT.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import raybatch, schedule, sparw
from repro.core.config import (  # noqa: F401 (RenderStats re-export)
    _UNSET,
    HoleCapController,
    RenderConfig,
    RenderStats,
    legacy_config,
)
from repro.nerf import rays
from repro.utils import round_up


class WindowResult(NamedTuple):
    """Device-side output of one jitted warp-window render."""

    frames: jnp.ndarray  # [N, H, W, 3]
    hole_counts: jnp.ndarray  # [N] int32 — true (uncapped) hole counts
    overflowed: jnp.ndarray  # [] bool — hole_cap exceeded, dense fallback ran
    fine_counts: jnp.ndarray  # [N] int32 — full-budget holes (== hole_counts
    #                           unless adaptive sampling split the pool)


class BatchedWindowResult(NamedTuple):
    """Device-side output of one jitted multi-session window render.

    Leading axis is the *session* (one concurrent client trajectory per
    row); the second axis is the session's warp window.
    """

    frames: jnp.ndarray  # [S, N, H, W, 3]
    hole_counts: jnp.ndarray  # [S, N] int32 — true (uncapped) hole counts
    overflowed: jnp.ndarray  # [S] bool — per-session dense-fallback flag
    fine_counts: jnp.ndarray  # [S, N] int32 — full-budget holes (feeds the
    #                           fine-pool controller; == hole_counts unless
    #                           adaptive sampling split the pool)


class DeviceSparwEngine:
    """Renders SPARW warp windows as single jitted device programs.

    Construct with ``config=RenderConfig(...)`` (the legacy
    ``(cam, window=..., ...)`` kwargs keep working behind a
    ``DeprecationWarning``). ``config.hole_cap`` is the static per-frame
    sparse-ray capacity (default: a quarter of the frame — paper hole
    fractions are 2–6%, so this leaves a wide margin before the dense
    fallback triggers). ``config.shard`` lays the session axis of
    ``render_windows`` over multiple devices.
    """

    _LEGACY_DEFAULTS = dict(window=16, phi_deg=None, hole_cap=None,
                            ray_chunk=RenderConfig.ray_chunk)

    def __init__(self, model, params: dict, cam: Optional[rays.Camera] = None,
                 window=_UNSET, phi_deg=_UNSET, hole_cap=_UNSET,
                 ray_chunk=_UNSET, *, config: Optional[RenderConfig] = None):
        config = legacy_config(
            "DeviceSparwEngine", cam, config, self._LEGACY_DEFAULTS,
            dict(window=window, phi_deg=phi_deg, hole_cap=hole_cap,
                 ray_chunk=ray_chunk))
        self.config = config
        self.model = model
        self.cam = config.camera
        self.window = config.window
        self.phi_deg = config.phi_deg
        hw = self.cam.height * self.cam.width
        self.hole_cap = (int(config.hole_cap) if config.hole_cap is not None
                         else round_up(max(hw // 4, 128), 128))
        # NOT capped at one frame's pixel count: the flat core's whole point
        # is that a cross-session batch fills one large contiguous chunk
        # (each call still takes min(ray_chunk, batch) — small batches never
        # over-pad)
        self.ray_chunk = int(config.ray_chunk)
        # streaming backend: MVoxel table built once here, never per frame;
        # the flat core then tags every ray with its session segment so the
        # gather drops chunk padding
        self.params = model.prepare_streaming(params)
        self._seg_aware = (getattr(model.cfg, "backend", "reference")
                           == "streaming"
                           and getattr(model.cfg, "kind", "") == "dvgo")
        # multi-device session sharding: one mesh per engine lifetime; the
        # model params (and MVoxel table) are replicated — one logical copy
        # serves every session on every device
        self.mesh = raybatch.make_mesh(config.shard)
        if self.mesh is not None:
            self.params = jax.device_put(
                self.params, raybatch.replicated_sharding(self.mesh))
        # --- pooled tick-level hole capacity + adaptive sampling ----------
        # One [S * bucket] pooled sparse batch per tick instead of the
        # worst-case [S*N*cap]; the bucket is a STATIC jit argument (pow2
        # ladder — bounded recompiles) while the per-session effective pool
        # capacities ride as traced [S] inputs, mirroring win_lens/caps.
        self.pool_holes = bool(config.pool_holes)
        self.pool_min_bucket = int(config.pool_min_bucket)
        self.adaptive_sampling = bool(config.adaptive_sampling)
        self.adaptive_var_threshold = float(config.adaptive_var_threshold)
        self.coarse_factor = int(config.coarse_factor)
        if self.adaptive_sampling and \
                model.cfg.num_samples % self.coarse_factor != 0:
            raise ValueError(
                f"adaptive_sampling needs the model's num_samples "
                f"({model.cfg.num_samples}) divisible by coarse_factor "
                f"({self.coarse_factor})")
        ctl_kw = dict(min_bucket=self.pool_min_bucket,
                      safety=config.pool_safety,
                      alpha=config.pool_ewma_alpha, fixed=config.pool_bucket)
        worst = self.window * self.hole_cap
        self.pool_ctl = HoleCapController(worst=worst, **ctl_kw)
        self.pool_ctl_coarse = HoleCapController(worst=worst, **ctl_kw)
        # every distinct (bucket, bucket_coarse) this engine compiled for —
        # tests assert the jit cache size tracks it (and stays <= ladder)
        self.pool_buckets_used: set = set()
        self.num_window_calls = 0  # jitted window invocations (tests assert)
        self._windows_jit = jax.jit(self._render_windows,
                                    static_argnums=(7, 8))
        # --- unified streaming tick (fused ref→warp→hole-fill) ------------
        # fused_tick routes render_trajectory AND the serving engine's
        # tick through ONE dual-RIT MVoxel sweep
        # (raybatch.render_tick_streaming); the staged _windows_jit stays
        # available (it is the bytes-moved baseline, the dense fallback,
        # and the fused_tick=False serve path)
        self.fused_tick = bool(getattr(config, "fused_tick", False))
        if self.fused_tick and not self._seg_aware:
            raise ValueError(
                "fused_tick requires a dvgo model on the streaming backend")
        self._tick_jit = jax.jit(self._tick_streaming, static_argnums=(9,))
        self._prime_jit = jax.jit(self._prime_reference)
        self._prime_select_jit = jax.jit(self._prime_select)
        # staged full-window/full-cap defaults per (S, N) so a default
        # render_windows call never rebuilds them (and the serving engine's
        # explicit arrays follow the same staging discipline)
        self._default_masks: Dict[Tuple[int, int],
                                  Tuple[jnp.ndarray, jnp.ndarray]] = {}
        # staged per-session pool capacities per (S, bucket, bucket_coarse)
        self._default_pool_caps: Dict[Tuple[int, int, int],
                                      Tuple[jnp.ndarray, jnp.ndarray]] = {}

    # ------------------------------------------------------------------
    @property
    def pool_ladder_size(self) -> int:
        """Bound on distinct (bucket, bucket_coarse) compile targets."""
        fine = self.pool_ctl.ladder_size
        return fine * (self.pool_ctl_coarse.ladder_size
                       if self.adaptive_sampling else 1)

    def _current_buckets(self) -> Tuple[int, int]:
        """The static pool bucket(s) the next dispatch compiles against
        (0 disables the pooled path / the coarse sub-pool)."""
        if not self.pool_holes:
            return 0, 0
        return (self.pool_ctl.bucket,
                self.pool_ctl_coarse.bucket if self.adaptive_sampling else 0)

    def _staged_pool_caps(self, s: int, bucket: int, bucket_coarse: int
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        staged = self._default_pool_caps.get((s, bucket, bucket_coarse))
        if staged is None:
            staged = (jnp.full((s,), bucket, jnp.int32),
                      jnp.full((s,), bucket_coarse, jnp.int32))
            self._default_pool_caps[(s, bucket, bucket_coarse)] = staged
        return staged

    # ------------------------------------------------------------------
    # fully in-graph primitives (all flat: no per-session vmap)
    # ------------------------------------------------------------------
    def _render_rays_flat(self, params: dict, o: jnp.ndarray, d: jnp.ndarray,
                          seg: Optional[jnp.ndarray], num_seg: int,
                          quantum: int, num_samples: Optional[int] = None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """ONE fused NeRF call over a flat [F,3] cross-session ray batch,
        chunked via ``lax.map`` — static shapes (pad + slice), bounded
        memory, no host loop. Chunk-padding rays are tagged with the dump
        segment ``num_seg`` so they never pollute a session's RIT.

        ``quantum`` is the stage's per-session ray count, and the chunk
        size is ``min(ray_chunk, ceil(quantum/2))`` — NEVER the whole
        flat batch, and never a whole per-session stage either. Two
        invariants make every session's rows bit-identical to its
        exclusive (S=1) run *by construction*:

        * the chunk body has the same shape at S=1 and S=k (XLA codegen
          is shape-dependent — differently-shaped bodies may differ in
          ulps), and
        * every arm's ``lax.map`` has trip count >= 2 (at quantum/2 the
          S=1 arm already loops twice), because XLA *elides* single-trip
          loops and fuses their body into the surrounding graph, which
          changes the generated code even for an identical body shape.

        Per-ray math is row-parallel, so with both invariants the same
        compiled loop body processes each ray in every arm. ``ray_chunk``
        stays the cache-blocking cap on top.

        Scope: the bit-parity guarantee covers the segment-oblivious
        (reference) backend, whose math is purely per-ray. The streaming
        backend's RIT is built per chunk; its contract is *numerical*
        parity with the reference path, not bitwise.
        """
        n = o.shape[0]
        c = min(self.ray_chunk, max(-(-quantum // 2), 1), n)
        npad = round_up(n, c)
        o = jnp.pad(o, ((0, npad - n), (0, 0)))
        d = jnp.pad(d, ((0, npad - n), (0, 0)))
        if seg is None:
            col, dep = jax.lax.map(
                lambda od: self.model.render_rays(
                    params, od[0], od[1], num_samples=num_samples),
                (o.reshape(-1, c, 3), d.reshape(-1, c, 3)))
        else:
            seg = jnp.pad(seg, (0, npad - n), constant_values=num_seg)
            col, dep = jax.lax.map(
                lambda ods: self.model.render_rays(
                    params, ods[0], ods[1], seg=ods[2], num_seg=num_seg,
                    num_samples=num_samples),
                (o.reshape(-1, c, 3), d.reshape(-1, c, 3),
                 seg.reshape(-1, c)))
        return col.reshape(npad, 3)[:n], dep.reshape(npad)[:n]

    def _dense_fill_flat(self, params: dict, tgt_poses: jnp.ndarray
                         ) -> jnp.ndarray:
        """Dense re-render of every target frame of every session — the
        overflow fallback, itself one flat batch. [S, N, HW, 3]."""
        s, n = tgt_poses.shape[0], tgt_poses.shape[1]
        hw = self.cam.height * self.cam.width
        o, d = rays.generate_rays_batch(self.cam, tgt_poses.reshape(-1, 4, 4))
        seg = (jnp.repeat(jnp.arange(s, dtype=jnp.int32), n * hw)
               if self._seg_aware else None)
        col, _ = self._render_rays_flat(params, o.reshape(-1, 3),
                                        d.reshape(-1, 3), seg, s,
                                        quantum=n * hw)
        return col.reshape(s, n, hw, 3)

    def _pooled_fill(self, params: dict, tgt_poses: jnp.ndarray,
                     holes: jnp.ndarray, live: jnp.ndarray, bucket: int,
                     num_samples: Optional[int] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """ONE fused sparse fill over a POOLED [S * bucket] hole batch.

        All of a session's window holes compact into one contiguous
        ``bucket``-slot region (statistical pooling across the window
        instead of worst-case per-frame capacity), render through one
        fused NeRF call, and segment-scatter back. The fill chunks at
        ``quantum=pool_min_bucket`` — a bucket-INDEPENDENT constant — so
        resizing the pool bucket never changes the compiled chunk body
        and every ray's math stays bit-identical across ladder steps
        (session regions start at multiples of the chunk size because
        ``bucket`` is a pow2 >= pool_min_bucket >= the chunk size).
        Returns ([S, N, HW, 3] sparse frames, [S] true live hole totals).
        """
        s, n = tgt_poses.shape[0], tgt_poses.shape[1]
        hw = self.cam.height * self.cam.width
        addr, totals = sparw.compact_holes_pooled(holes, bucket, live)
        batch, flat_addr = raybatch.pack_hole_rays_pooled(
            self.cam, tgt_poses, addr)
        fill_col, _ = self._render_rays_flat(
            params, batch.origins, batch.dirs,
            batch.seg if self._seg_aware else None, s,
            quantum=self.pool_min_bucket, num_samples=num_samples)
        valid = (jnp.arange(bucket)[None, :] < totals[:, None]).reshape(-1)
        sparse = raybatch.scatter_segments(fill_col, flat_addr, valid,
                                           s * n * hw)
        return sparse.reshape(s, n, hw, 3), totals

    def _render_windows(self, params: dict, ref_poses: jnp.ndarray,
                        tgt_poses: jnp.ndarray, win_lens: jnp.ndarray,
                        caps: jnp.ndarray, pool_caps: jnp.ndarray,
                        pool_caps_coarse: jnp.ndarray, bucket: int,
                        bucket_coarse: int) -> BatchedWindowResult:
        """S concurrent sessions' windows — ONE traced function built from
        flat cross-session stages (see the module docstring for the ①–④
        walk-through).

        The overflow fallback is *per session*: a session that exceeds its
        hole capacity takes its frames from the dense branch while its
        neighbours keep the sparse-path output bit-for-bit (the dense
        branch is guarded by a single ``lax.cond`` so the no-overflow
        steady state compiles to the sparse path only).

        ``win_lens`` [S] and ``caps`` [S] carry the per-session overrides
        that let *ragged* windows batch into this one program: a session
        whose window is shorter than N pads its targets (padded frames are
        rendered and discarded on the host) and the window-length mask
        excludes those pads from the overflow decision; ``caps`` is the
        session's effective hole capacity (≤ the engine's static
        ``hole_cap``, which fixes the compaction shape). Both are traced
        inputs — value changes never recompile the program.

        ``bucket`` / ``bucket_coarse`` are STATIC pool-bucket sizes (pow2
        ladder, so the recompile count is bounded by the ladder);
        ``pool_caps`` / ``pool_caps_coarse`` [S] are the traced
        per-session effective pool capacities (a session's own controller
        bucket — it overflows to dense when its window total exceeds its
        own budget even if the tick's shared bucket is larger, keeping
        the overflow decision identical to its exclusive run).
        ``bucket == 0`` selects the legacy per-frame fixed-capacity
        batch; ``bucket_coarse == 0`` disables the adaptive coarse
        sub-pool.
        """
        s, n = tgt_poses.shape[0], tgt_poses.shape[1]
        h, w = self.cam.height, self.cam.width
        hw = h * w
        cap = self.hole_cap
        # ① ONE fused reference render across all sessions' rays
        ref = raybatch.pack_reference_rays(self.cam, ref_poses)
        col, dep = self._render_rays_flat(
            params, ref.origins, ref.dirs,
            ref.seg if self._seg_aware else None, s, quantum=hw)
        rgb_ref = col.reshape(s, h, w, 3)
        dep_ref = dep.reshape(s, h, w)
        # ②③ one flat warp scatter pass + flat hole compaction
        warped = sparw.warp_frames_flat(rgb_ref, dep_ref, ref_poses,
                                        tgt_poses, self.cam,
                                        phi_deg=self.phi_deg)
        holes = warped.holes.reshape(s, n, hw)
        # per-session window-length mask: padded frames past win_lens[s]
        # must not trip that session's dense fallback
        live = jnp.arange(n)[None, :] < win_lens[:, None]  # [S, N]
        counts = jnp.sum(holes & live[:, :, None], axis=2)  # [S, N] true
        frame_over = jnp.max(jnp.where(live, counts, 0), axis=1) > caps
        fine_counts = counts
        if bucket == 0:
            # legacy per-frame fixed-capacity flat batch [S*N*cap]
            idx, _ = sparw.compact_holes_flat(holes, cap)
            overflowed = frame_over
            # ④ ONE fused sparse fill over the tick's flat hole batch,
            # then segment-scatter back to frames
            batch, addr = raybatch.pack_hole_rays(self.cam, tgt_poses, idx)
            fill_col, _ = self._render_rays_flat(
                params, batch.origins, batch.dirs,
                batch.seg if self._seg_aware else None, s, quantum=n * cap)
            valid = (jnp.arange(cap)[None, None, :] < counts[..., None])
            sparse = raybatch.scatter_segments(
                fill_col, addr, valid.reshape(-1), s * n * hw)
            sparse = sparse.reshape(s, n, hw, 3)
        elif bucket_coarse == 0:
            # ④ pooled: the whole tick's holes share ONE [S*bucket] batch
            sparse, totals = self._pooled_fill(params, tgt_poses, holes,
                                               live, bucket)
            overflowed = frame_over | (totals > pool_caps)
        else:
            # ④ pooled + ASDR-style adaptive sampling: split holes by
            # warped-neighborhood disagreement — unreliable (few warped
            # neighbors / high radiance variance) rays keep the full
            # sample budget, agreeing rays drop to num_samples/coarse_factor
            var, cnt = sparw.warp_disagreement(warped.rgb, warped.holes)
            fine_m = warped.holes & (
                (cnt < 3) | (var > self.adaptive_var_threshold))
            fine = fine_m.reshape(s, n, hw) & live[:, :, None]
            coarse = holes & live[:, :, None] & ~fine
            sparse_f, tot_f = self._pooled_fill(params, tgt_poses, fine,
                                                live, bucket)
            sparse_c, tot_c = self._pooled_fill(
                params, tgt_poses, coarse, live, bucket_coarse,
                num_samples=self.model.cfg.num_samples // self.coarse_factor)
            sparse = sparse_f + sparse_c  # disjoint masks — no overlap
            overflowed = (frame_over | (tot_f > pool_caps)
                          | (tot_c > pool_caps_coarse))
            fine_counts = jnp.sum(fine, axis=2)
        with jax.named_scope("dense_fallback"):
            dense = jax.lax.cond(
                jnp.any(overflowed),
                lambda _: self._dense_fill_flat(params, tgt_poses),
                lambda _: jnp.zeros_like(sparse),
                None)
            fill = jnp.where(overflowed[:, None, None, None], dense, sparse)
        with jax.named_scope("composite"):
            frames = jnp.where(holes[..., None], fill,
                               warped.rgb.reshape(s, n, hw, 3))
        return BatchedWindowResult(frames.reshape(s, n, h, w, 3),
                                   counts.astype(jnp.int32), overflowed,
                                   fine_counts.astype(jnp.int32))

    # ------------------------------------------------------------------
    def _staged_masks(self, s: int, n: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        staged = self._default_masks.get((s, n))
        if staged is None:
            staged = (jnp.full((s,), n, jnp.int32),
                      jnp.full((s,), self.hole_cap, jnp.int32))
            self._default_masks[(s, n)] = staged
        return staged

    def render_window(self, ref_pose: jnp.ndarray, tgt_poses: jnp.ndarray
                      ) -> WindowResult:
        """Render one warp window (N target poses vs a shared reference) as
        a single jitted call — the flat program at S=1, so an exclusive run
        executes exactly the batched per-session code path. ``jax.jit``
        re-traces only per distinct N."""
        n = tgt_poses.shape[0]
        win_lens, caps = self._staged_masks(1, n)
        bucket, bucket_c = self._current_buckets()
        pool_caps, pool_caps_c = self._staged_pool_caps(1, bucket, bucket_c)
        self.pool_buckets_used.add((bucket, bucket_c))
        self.num_window_calls += 1
        res = self._windows_jit(self.params, ref_pose[None], tgt_poses[None],
                                win_lens, caps, pool_caps, pool_caps_c,
                                bucket, bucket_c)
        # static squeezes (not [0]-indexing, which would stage a host index
        # constant and trip the zero-host-sync transfer guard)
        return WindowResult(jnp.squeeze(res.frames, 0),
                            jnp.squeeze(res.hole_counts, 0),
                            jnp.squeeze(res.overflowed, 0),
                            jnp.squeeze(res.fine_counts, 0))

    def render_windows(self, ref_poses: jnp.ndarray, tgt_poses: jnp.ndarray,
                       win_lens: Optional[jnp.ndarray] = None,
                       caps: Optional[jnp.ndarray] = None,
                       pool_caps: Optional[jnp.ndarray] = None,
                       pool_caps_coarse: Optional[jnp.ndarray] = None,
                       bucket: Optional[int] = None,
                       bucket_coarse: Optional[int] = None
                       ) -> BatchedWindowResult:
        """Render S sessions' warp windows ([S,4,4] refs vs [S,N,4,4]
        targets) as a single jitted call — the multi-session serving tick.

        ``win_lens``/``caps`` ([S] int32 device arrays) carry per-session
        window-length / hole-capacity overrides; omitted they default to
        the full window and the engine's static capacity (staged once per
        (S, N), so the default path stays transfer-free after warm-up).
        Re-traces only per distinct (S, N); a fixed-slot serving engine
        therefore compiles exactly one program for its whole lifetime.

        With ``config.shard`` enabled the session axis is laid over the
        device mesh (S must divide evenly; sessions are pinned whole).
        """
        s, n = tgt_poses.shape[0], tgt_poses.shape[1]
        if win_lens is None or caps is None:
            staged = self._staged_masks(s, n)
            win_lens = staged[0] if win_lens is None else win_lens
            caps = staged[1] if caps is None else caps
        if bucket is None or bucket_coarse is None:
            cur = self._current_buckets()
            bucket = cur[0] if bucket is None else bucket
            bucket_coarse = cur[1] if bucket_coarse is None else bucket_coarse
        if pool_caps is None or pool_caps_coarse is None:
            staged = self._staged_pool_caps(s, bucket, bucket_coarse)
            pool_caps = staged[0] if pool_caps is None else pool_caps
            pool_caps_coarse = (staged[1] if pool_caps_coarse is None
                                else pool_caps_coarse)
        if self.mesh is not None and s > 1:
            ndev = self.mesh.devices.size
            if s % ndev != 0:
                raise ValueError(
                    f"render_windows: {s} sessions cannot shard evenly "
                    f"over {ndev} devices")
            (ref_poses, tgt_poses, win_lens, caps, pool_caps,
             pool_caps_coarse) = raybatch.shard_session_inputs(
                self.mesh, ref_poses, tgt_poses, win_lens, caps,
                pool_caps, pool_caps_coarse)
        self.pool_buckets_used.add((bucket, bucket_coarse))
        self.num_window_calls += 1
        return self._windows_jit(self.params, ref_poses, tgt_poses,
                                 win_lens, caps, pool_caps,
                                 pool_caps_coarse, bucket, bucket_coarse)

    # ------------------------------------------------------------------
    # unified streaming tick (fused reference → warp → hole-fill)
    # ------------------------------------------------------------------
    def _prime_reference(self, params: dict, ref_poses: jnp.ndarray
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Render the pipeline-priming reference frames ([S,4,4] poses →
        ([S,H,W,3], [S,H,W])) — the staged flat reference stage, run ONCE
        per trajectory before the fused ticks take over (every later
        reference comes out of a fused sweep)."""
        s = ref_poses.shape[0]
        h, w = self.cam.height, self.cam.width
        ref = raybatch.pack_reference_rays(self.cam, ref_poses)
        col, dep = self._render_rays_flat(params, ref.origins, ref.dirs,
                                          ref.seg, s, quantum=h * w)
        return col.reshape(s, h, w, 3), dep.reshape(s, h, w)

    def prime_reference(self, ref_poses: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return self._prime_jit(self.params, ref_poses)

    def _prime_select(self, params: dict, prime_poses: jnp.ndarray,
                      mask: jnp.ndarray, rgb_ref: jnp.ndarray,
                      dep_ref: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        rgb_p, dep_p = self._prime_reference(params, prime_poses)
        return raybatch.substitute_reference_rows(mask, rgb_p, dep_p,
                                                  rgb_ref, dep_ref)

    def prime_reference_select(self, prime_poses: jnp.ndarray,
                               mask: jnp.ndarray, rgb_ref: jnp.ndarray,
                               dep_ref: jnp.ndarray
                               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Mid-stream admission priming for the SERVING fused tick: render
        the ``[S, 4, 4]`` poses through the staged flat reference stage and
        substitute ONLY the rows where ``mask`` is True into the running
        cross-tick recurrence (``rgb_ref``/``dep_ref``). Continuing
        sessions' co-rendered references pass through bitwise untouched;
        a reused slot's row is fully overwritten by the new occupant's
        prime before any warp reads it. The dispatch shape is always the
        full slot batch — one compile per S for the engine lifetime,
        regardless of how many slots an admission tick fills."""
        return self._prime_select_jit(self.params, prime_poses, mask,
                                      rgb_ref, dep_ref)

    def _tick_streaming(self, params: dict, rgb_ref: jnp.ndarray,
                        dep_ref: jnp.ndarray, ref_poses: jnp.ndarray,
                        tgt_poses: jnp.ndarray, next_ref_poses: jnp.ndarray,
                        win_lens: jnp.ndarray, caps: jnp.ndarray,
                        pool_caps: jnp.ndarray, bucket: int
                        ) -> raybatch.StreamingTickResult:
        return raybatch.render_tick_streaming(
            self.model, params, self.cam, phi_deg=self.phi_deg,
            rgb_ref=rgb_ref, dep_ref=dep_ref, ref_poses=ref_poses,
            tgt_poses=tgt_poses, next_ref_poses=next_ref_poses,
            win_lens=win_lens, caps=caps, pool_caps=pool_caps,
            bucket=bucket,
            dense_fill=lambda tp: self._dense_fill_flat(params, tp))

    def render_windows_streaming(self, rgb_ref: jnp.ndarray,
                                 dep_ref: jnp.ndarray,
                                 ref_poses: jnp.ndarray,
                                 tgt_poses: jnp.ndarray,
                                 next_ref_poses: jnp.ndarray,
                                 win_lens: Optional[jnp.ndarray] = None,
                                 caps: Optional[jnp.ndarray] = None,
                                 pool_caps: Optional[jnp.ndarray] = None,
                                 bucket: Optional[int] = None
                                 ) -> raybatch.StreamingTickResult:
        """One unified streaming tick for S sessions: warp the references
        rendered LAST tick (``rgb_ref``/``dep_ref`` @ ``ref_poses``) into
        ``tgt_poses``, fill the pooled holes AND render
        ``next_ref_poses``'s frames through one fused MVoxel sweep. The
        returned ``next_rgb_ref``/``next_dep_ref`` feed the next call —
        cross-tick software pipelining. Same staging/ladder discipline as
        :meth:`render_windows`; re-traces only per (S, N, bucket)."""
        s, n = tgt_poses.shape[0], tgt_poses.shape[1]
        if win_lens is None or caps is None:
            staged = self._staged_masks(s, n)
            win_lens = staged[0] if win_lens is None else win_lens
            caps = staged[1] if caps is None else caps
        if bucket is None:
            bucket = self._current_buckets()[0]
        if pool_caps is None:
            pool_caps = self._staged_pool_caps(s, bucket, 0)[0]
        if bucket == 0:
            raise ValueError("the fused streaming tick requires a pooled "
                             "hole bucket (pool_holes=True)")
        self.pool_buckets_used.add((bucket, 0))
        self.num_window_calls += 1
        return self._tick_jit(self.params, rgb_ref, dep_ref, ref_poses,
                              tgt_poses, next_ref_poses, win_lens, caps,
                              pool_caps, bucket)

    # ------------------------------------------------------------------
    # per-tick bytes-moved accounting (staged vs fused MVoxel traffic)
    # ------------------------------------------------------------------
    def _staged_chunk_sweeps(self, n_rays: int, quantum: int) -> int:
        """How many ``lax.map`` chunks one staged flat stage runs — each
        chunk is one full MVoxel-table sweep (its ``pallas_call`` grid
        iterates every halo block). Mirrors ``_render_rays_flat``'s chunk
        math exactly."""
        if n_rays == 0:
            return 0
        c = min(self.ray_chunk, max(-(-quantum // 2), 1), n_rays)
        return round_up(n_rays, c) // c

    def tick_memory_stats(self, sessions: int, window: Optional[int] = None,
                          bucket: Optional[int] = None) -> Dict[str, float]:
        """Analytic per-tick MVoxel-table traffic: staged vs fused.

        The staged tick re-streams the FULL halo table once per ray chunk
        of every stage (reference + pooled fill); the fused tick streams
        it exactly once. Counted from the same chunk math the compiled
        programs use — deterministic, no profiling. The XLA-side
        cross-check (total HLO bytes) lives in ``roofline.hlo_cost``;
        this is the Pallas-side analytic count the ISSUE's
        ``bytes_moved_per_frame`` gate runs on.
        """
        n = int(window) if window is not None else self.window
        s = int(sessions)
        hw = self.cam.height * self.cam.width
        if bucket is None:
            bucket = self._current_buckets()[0]
        scfg = self.model.streaming_cfg
        chans = self.model.cfg.feat_channels
        block_bytes = scfg.halo_rows * chans * 4
        table_bytes = scfg.num_mvoxels * block_bytes
        ref_sweeps = self._staged_chunk_sweeps(s * hw, hw)
        if bucket > 0:
            fill_sweeps = self._staged_chunk_sweeps(s * bucket,
                                                    self.pool_min_bucket)
        else:
            fill_sweeps = self._staged_chunk_sweeps(
                s * n * self.hole_cap, n * self.hole_cap)
        staged_sweeps = ref_sweeps + fill_sweeps
        frames = s * n
        return {
            "sessions": float(s),
            "window": float(n),
            "pool_bucket": float(bucket),
            "mvoxel_table_bytes": float(table_bytes),
            "staged_table_sweeps_per_tick": float(staged_sweeps),
            "staged_ref_sweeps": float(ref_sweeps),
            "staged_fill_sweeps": float(fill_sweeps),
            "staged_mvoxel_bytes_per_tick": float(staged_sweeps
                                                  * table_bytes),
            "staged_mvoxel_bytes_per_frame": staged_sweeps * table_bytes
            / frames,
            "fused_table_sweeps_per_tick": 1.0,
            "fused_mvoxel_bytes_per_tick": float(table_bytes),
            "fused_mvoxel_bytes_per_frame": table_bytes / frames,
            "bytes_reduction_staged_over_fused": float(staged_sweeps),
        }

    def _observe_window(self, res) -> None:
        """Feed one finished window's hole totals to the pool controllers
        (host-side, between dispatches — the compiled program never sees
        the controller)."""
        if not self.pool_holes:
            return
        counts = np.asarray(res.hole_counts)
        fine = np.asarray(res.fine_counts)
        self.pool_ctl.observe(int(fine.sum()))
        if self.adaptive_sampling:
            self.pool_ctl_coarse.observe(int(counts.sum() - fine.sum()))

    def render_trajectory(self, poses: List[jnp.ndarray]
                          ) -> Tuple[List[jnp.ndarray], RenderStats]:
        """SPARW rendering of a pose trajectory (offtraj schedule).

        Statistics read back with a TWO-window pipeline delay: before
        dispatching window ``i`` the pool controllers observe window
        ``i-2`` — exactly the cadence of the serving engine's tick loop
        (dispatch tick i, then finalize ticks ≤ i-1, whose observations
        land before dispatch i+1), so an exclusive trajectory and a serve
        run walk the same pool-bucket ladder. Controllers reset at entry:
        a cached engine behaves like a fresh one. Frames/stats convert
        after all dispatches, so pooling adds no *extra* syncs beyond the
        pipelined count readbacks (none at all when pooling is off).
        """
        if self.fused_tick:
            return self._render_trajectory_fused(poses)
        plan = schedule.WarpSchedule(self.window, "offtraj").windows(poses)
        hw = self.cam.height * self.cam.width
        frames_out: List[Optional[jnp.ndarray]] = [None] * len(poses)
        stats = RenderStats()
        results = []
        self.pool_ctl.reset()
        self.pool_ctl_coarse.reset()
        pending_obs: List[WindowResult] = []
        for win in plan:
            if self.pool_holes and len(pending_obs) >= 2:
                self._observe_window(pending_obs.pop(0))
            tgt = jnp.stack([poses[i] for i in win["frames"]])
            res = self.render_window(win["ref_pose"], tgt)
            results.append((win["frames"], res))
            pending_obs.append(res)
            stats.reference_renders += 1
        for idxs, res in results:  # host conversion after all dispatches
            counts = np.asarray(res.hole_counts)
            ovf = bool(res.overflowed)
            for j, f in enumerate(idxs):
                frames_out[f] = res.frames[j]
                stats.record_frame(int(counts[j]), ovf, hw)
        return [f for f in frames_out if f is not None], stats

    def _render_trajectory_fused(self, poses: List[jnp.ndarray]
                                 ) -> Tuple[List[jnp.ndarray], RenderStats]:
        """Trajectory rendering through the unified streaming tick.

        Same offtraj schedule, pool-controller cadence and host-conversion
        discipline as the staged loop, but each window is ONE fused
        MVoxel sweep: tick ``i`` warps the reference that tick ``i-1``'s
        sweep rendered and co-renders tick ``i+1``'s reference
        (cross-tick software pipelining; the first reference is primed by
        the staged flat reference stage). The last tick re-renders its
        own reference as the next-ref placeholder — one warm-schedule
        sweep, output discarded.
        """
        plan = list(schedule.WarpSchedule(self.window, "offtraj")
                    .windows(poses))
        hw = self.cam.height * self.cam.width
        frames_out: List[Optional[jnp.ndarray]] = [None] * len(poses)
        stats = RenderStats()
        results = []
        self.pool_ctl.reset()
        self.pool_ctl_coarse.reset()
        pending_obs: List[raybatch.StreamingTickResult] = []
        ref_pose = plan[0]["ref_pose"][None]
        rgb_ref, dep_ref = self.prime_reference(ref_pose)
        stats.reference_renders += 1  # the priming render
        for i, win in enumerate(plan):
            if self.pool_holes and len(pending_obs) >= 2:
                self._observe_window(pending_obs.pop(0))
            tgt = jnp.stack([poses[j] for j in win["frames"]])[None]
            next_pose = (plan[i + 1]["ref_pose"][None]
                         if i + 1 < len(plan) else ref_pose)
            res = self.render_windows_streaming(rgb_ref, dep_ref, ref_pose,
                                                tgt, next_pose)
            rgb_ref, dep_ref = res.next_rgb_ref, res.next_dep_ref
            ref_pose = next_pose
            results.append((win["frames"], res))
            pending_obs.append(res)
            stats.reference_renders += 1
        for idxs, res in results:  # host conversion after all dispatches
            counts = np.asarray(res.hole_counts)[0]
            ovf = bool(np.asarray(res.overflowed)[0])
            for j, f in enumerate(idxs):
                frames_out[f] = res.frames[0, j]
                stats.record_frame(int(counts[j]), ovf, hw)
        return [f for f in frames_out if f is not None], stats
