"""Flat ray-batch execution core (cross-session fusion + session sharding).

PR 3's multi-session engine batched S sessions by ``vmap``-ing the whole
per-session pipeline over a leading session axis. That regularizes
*dispatch* (one device call per tick) but not *dataflow*: the NeRF
evaluation still runs as S small per-session programs whose vmapped
scatter/gather order costs more than the dispatch it saves (the measured
warm batched-vs-sequential ratio was ~0.5× on CPU). Potamoi's unified
streaming pipeline and RT-NeRF's dense-batch regularization both make the
same point at the architecture level: pack the sparse, per-client work
into ONE flat, contiguous stream *before* the expensive stages.

This module is that packing layer. A tick's work becomes one **flat ray
batch**:

* every session's reference rays pack to ``[S * HW, 3]`` (session-major),
* every (session, frame)'s compacted hole samples pack to
  ``[S * N * cap, 3]`` — the fixed-capacity flat batch, with segment ids
  mapping each row back to its ``(session, frame)``,
* ONE fused reference render + ONE sparse-fill NeRF call run over these
  flat batches (the Pallas kernels finally see large contiguous inputs),
* results **segment-scatter** back to ``[S, N, H, W, 3]`` frames.

Because the flat layout is session-major, laying a
``jax.sharding.NamedSharding`` over the leading session axis
(:class:`~repro.core.config.ShardConfig`) pins each session's rays,
samples and frames to one device — the segment scatters never cross a
device boundary. Single-device execution is bit-identical to the
unsharded engine.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import ShardConfig
from repro.nerf import rays


class FlatRays(NamedTuple):
    """A flat, session-major ray batch: the unit of fused NeRF work.

    ``seg`` maps every ray to its owning *session* (``[0, num_seg)``) —
    the streaming backend buckets its Ray Index Table per (segment,
    MVoxel) so each session keeps exclusive-run capacity semantics inside
    the one fused gather. Rays appended as chunk padding use segment id
    ``num_seg`` (the dump segment: no capacity consumed, output ignored).
    """

    origins: jnp.ndarray  # [F, 3]
    dirs: jnp.ndarray     # [F, 3]
    seg: jnp.ndarray      # [F] int32 — owning session per ray


@jax.named_scope("compact")
def pack_reference_rays(cam: rays.Camera, ref_poses: jnp.ndarray) -> FlatRays:
    """All S sessions' reference-frame rays as ONE flat batch [S*HW, 3]."""
    s = ref_poses.shape[0]
    hw = cam.height * cam.width
    o, d = rays.generate_rays_batch(cam, ref_poses)  # [S, HW, 3]
    seg = jnp.repeat(jnp.arange(s, dtype=jnp.int32), hw)
    return FlatRays(o.reshape(-1, 3), d.reshape(-1, 3), seg)


@jax.named_scope("compact")
def pack_hole_rays(cam: rays.Camera, tgt_poses: jnp.ndarray,
                   idx: jnp.ndarray) -> Tuple[FlatRays, jnp.ndarray]:
    """The tick's compacted hole samples as ONE fixed-capacity flat batch.

    ``tgt_poses`` is ``[S, N, 4, 4]``, ``idx`` the ``[S, N, cap]`` compacted
    hole pixel ids (:func:`repro.core.sparw.compact_holes_flat`). Returns
    (flat rays ``[S*N*cap]``, and the flat *pixel addresses*
    ``[S*N*cap]`` — ``(s*N + n) * HW + pixel`` — used to segment-scatter
    rendered colors back into frames). Rows past a frame's true hole count
    alias its pixel 0 (exactly like the per-frame compaction) and are
    masked at scatter time.
    """
    s, n, cap = idx.shape
    hw = cam.height * cam.width
    b = s * n
    o_all, d_all = rays.generate_rays_batch(
        cam, tgt_poses.reshape(b, 4, 4))  # [B, HW, 3]
    # flat gather of the compacted rays: one address space over the tick
    seg_off = (jnp.arange(b, dtype=jnp.int32) * hw).reshape(s, n, 1)
    addr = (seg_off + idx).reshape(-1)  # [S*N*cap] flat ray/pixel address
    osel = o_all.reshape(-1, 3)[addr]
    dsel = d_all.reshape(-1, 3)[addr]
    seg = jnp.repeat(jnp.arange(s, dtype=jnp.int32), n * cap)
    return FlatRays(osel, dsel, seg), addr


@jax.named_scope("compact")
def pack_hole_rays_pooled(cam: rays.Camera, tgt_poses: jnp.ndarray,
                          addr: jnp.ndarray) -> Tuple[FlatRays, jnp.ndarray]:
    """The tick's POOLED hole samples as one ``[S * bucket]`` flat batch.

    ``addr`` is the ``[S, bucket]`` frame-local sample addresses
    (``n*HW + pixel``) from
    :func:`repro.core.sparw.compact_holes_pooled` — session ``s`` owns the
    contiguous region ``[s*bucket, (s+1)*bucket)`` of the flat batch, so
    segment ids stay session-major and (under session sharding) no gather
    or scatter crosses a device boundary. Returns (flat rays, the flat
    *global* pixel addresses ``s*N*HW + local`` used to segment-scatter
    rendered colors back into frames). Rows past a session's true hole
    total alias its frame 0 / pixel 0 and are masked at scatter time.
    """
    s, bucket = addr.shape
    n = tgt_poses.shape[1]
    hw = cam.height * cam.width
    o_all, d_all = rays.generate_rays_batch(
        cam, tgt_poses.reshape(s * n, 4, 4))  # [S*N, HW, 3]
    flat_addr = (jnp.arange(s, dtype=jnp.int32)[:, None] * (n * hw)
                 + addr).reshape(-1)  # [S*bucket] global sample address
    osel = o_all.reshape(-1, 3)[flat_addr]
    dsel = d_all.reshape(-1, 3)[flat_addr]
    seg = jnp.repeat(jnp.arange(s, dtype=jnp.int32), bucket)
    return FlatRays(osel, dsel, seg), flat_addr


@jax.named_scope("composite")
def scatter_segments(values: jnp.ndarray, addr: jnp.ndarray,
                     valid: jnp.ndarray, size: int) -> jnp.ndarray:
    """Segment-scatter flat results back to frame pixels: ONE scatter.

    ``values`` ``[F, C]`` land at flat pixel ``addr`` ``[F]`` of a
    ``[size, C]`` zero buffer; rows with ``valid`` False are dropped
    (their address is pushed out of range — ``mode="drop"`` keeps the
    scatter in-graph with a static shape, no host ``nonzero``).
    """
    tgt = jnp.where(valid, addr, size)
    return jnp.zeros((size, values.shape[-1]), values.dtype).at[tgt].set(
        values, mode="drop")


# ---------------------------------------------------------------------------
# unified streaming tick (fused reference → warp → hole-fill)
# ---------------------------------------------------------------------------


class StreamingTickResult(NamedTuple):
    """One fused tick's outputs plus the reference state it hands to the
    next tick (cross-tick software pipelining: tick ``t`` warps the
    reference that tick ``t-1``'s fused gather rendered, and renders tick
    ``t+1``'s reference in the same MVoxel-table sweep)."""

    frames: jnp.ndarray       # [S, N, H, W, 3]
    hole_counts: jnp.ndarray  # [S, N] int32 — true (uncapped) hole counts
    overflowed: jnp.ndarray   # [S] bool — per-session dense-fallback flag
    fine_counts: jnp.ndarray  # [S, N] int32 (== hole_counts; no adaptive
    #                           split on the fused path)
    next_rgb_ref: jnp.ndarray  # [S, H, W, 3] — tick t+1's reference frames
    next_dep_ref: jnp.ndarray  # [S, H, W]
    # [3, 2] int32 — rows 0 and 1 (hole stage, reference stage): (samples
    # that spilled past the RIT, always 0 since the RIT is ragged; live
    # samples gathered), pooled padding rows not counted; row 2: (pad
    # columns, columns) in the live blocks of the tick's merged sweep
    rit_counts: jnp.ndarray


def render_tick_streaming(model, params: dict, cam: rays.Camera, *,
                          phi_deg: Optional[float],
                          rgb_ref: jnp.ndarray, dep_ref: jnp.ndarray,
                          ref_poses: jnp.ndarray, tgt_poses: jnp.ndarray,
                          next_ref_poses: jnp.ndarray,
                          win_lens: jnp.ndarray, caps: jnp.ndarray,
                          pool_caps: jnp.ndarray, bucket: int,
                          dense_fill=None) -> StreamingTickResult:
    """The unified streaming tick: warp → pooled compaction → ONE fused
    Pallas gather serving BOTH the tick's hole fill and the NEXT tick's
    reference render → decode → composite → segment-scatter.

    Where the staged tick (``engine._render_windows``) runs reference
    render and hole fill as separate chunked programs — each ``lax.map``
    chunk re-streaming the full MVoxel table — this path bundles the
    pooled hole samples with the next reference's samples into one
    ragged-RIT sweep (``kernels.streaming_pipeline.gather_features_tick``),
    so every MVoxel halo block is fetched at most once per tick. The
    reference consumed here (``rgb_ref``/``dep_ref``, posed at
    ``ref_poses``) was produced by the *previous* tick (or by
    ``DeviceSparwEngine.prime_reference`` at trajectory start).

    ``bucket`` is the static pooled hole capacity (pow2 ladder);
    ``win_lens``/``caps``/``pool_caps`` are the traced per-session masks,
    identical in meaning to the staged path's. ``dense_fill`` is the
    per-session overflow fallback, ``tgt_poses -> [S, N, HW, 3]``
    (the engine passes its flat dense renderer).
    Requires a pooled dvgo/streaming model (``RenderConfig.fused_tick``
    validation enforces this).
    """
    from repro.core import sparw
    from repro.kernels import streaming_pipeline
    from repro.nerf import volrend

    s, n = tgt_poses.shape[0], tgt_poses.shape[1]
    h, w = cam.height, cam.width
    hw = h * w
    c = model.cfg
    ns = c.num_samples
    # ②③ warp LAST tick's reference into this tick's targets + pool holes
    warped = sparw.warp_frames_flat(rgb_ref, dep_ref, ref_poses, tgt_poses,
                                    cam, phi_deg=phi_deg)
    with jax.named_scope("compact"):
        holes = warped.holes.reshape(s, n, hw)
        live = jnp.arange(n)[None, :] < win_lens[:, None]
        counts = jnp.sum(holes & live[:, :, None], axis=2)
        frame_over = jnp.max(jnp.where(live, counts, 0), axis=1) > caps
    addr, totals = sparw.compact_holes_pooled(holes, bucket, live)
    hole_batch, flat_addr = pack_hole_rays_pooled(cam, tgt_poses, addr)
    ref_batch = pack_reference_rays(cam, next_ref_poses)
    # ①④ fused: sample both ray sets, gather through ONE table sweep
    pts_h, t_h = rays.sample_along_rays(hole_batch.origins, hole_batch.dirs,
                                        c.near, c.far, ns, None)
    pts_r, t_r = rays.sample_along_rays(ref_batch.origins, ref_batch.dirs,
                                        c.near, c.far, ns, None)
    # a mixed-scene slot batch reads each segment's page of the stacked
    # resident set through the traced segment→page map (scene churn
    # re-steers this program without recompiling)
    feats = streaming_pipeline.gather_features_tick(
        params["mv_table"], model.streaming_cfg,
        pts_h.reshape(-1, 3), jnp.repeat(hole_batch.seg, ns),
        pts_r.reshape(-1, 3), jnp.repeat(ref_batch.seg, ns),
        num_seg=s, scene_of_seg=params.get("scene_of_seg"),
        interpret=c.pallas_interpret)
    sig_h, rgb_h = model.decode_features(
        params, feats.hole, jnp.repeat(hole_batch.dirs, ns, axis=0))
    sig_r, rgb_r = model.decode_features(
        params, feats.ref, jnp.repeat(ref_batch.dirs, ns, axis=0))
    fill_col, _, _ = volrend.composite(sig_h.reshape(-1, ns),
                                       rgb_h.reshape(-1, ns, 3), t_h,
                                       c.far, c.white_bkgd)
    ref_col, ref_dep, _ = volrend.composite(sig_r.reshape(-1, ns),
                                            rgb_r.reshape(-1, ns, 3), t_r,
                                            c.far, c.white_bkgd)
    # segment-scatter the sparse fill back to frames
    valid = (jnp.arange(bucket)[None, :] < totals[:, None]).reshape(-1)
    sparse = scatter_segments(fill_col, flat_addr, valid,
                              s * n * hw).reshape(s, n, hw, 3)
    with jax.named_scope("rit_build"):
        # the ragged RIT spills nothing: rows 0 and 1 count live samples
        # beside a spill of 0; row 2 is the sweep's padding
        zero = jnp.zeros((), jnp.int32)
        rit_counts = jnp.stack([
            jnp.stack([zero, jnp.sum(valid) * ns]),
            jnp.stack([zero, jnp.asarray(feats.ref.shape[0])]),
            jnp.stack([feats.pad_columns, feats.columns]),
        ]).astype(jnp.int32)
    overflowed = frame_over | (totals > pool_caps)
    fill = sparse
    if dense_fill is not None:
        with jax.named_scope("dense_fallback"):
            dense = jax.lax.cond(jnp.any(overflowed),
                                 lambda _: dense_fill(tgt_poses),
                                 lambda _: jnp.zeros_like(sparse), None)
            fill = jnp.where(overflowed[:, None, None, None], dense, sparse)
    with jax.named_scope("composite"):
        frames = jnp.where(holes[..., None], fill,
                           warped.rgb.reshape(s, n, hw, 3))
    return StreamingTickResult(
        frames.reshape(s, n, h, w, 3), counts.astype(jnp.int32),
        overflowed, counts.astype(jnp.int32),
        ref_col.reshape(s, h, w, 3), ref_dep.reshape(s, h, w), rit_counts)


@jax.named_scope("composite")
def substitute_reference_rows(mask: jnp.ndarray, rgb_new: jnp.ndarray,
                              dep_new: jnp.ndarray, rgb_ref: jnp.ndarray,
                              dep_ref: jnp.ndarray
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-select freshly primed reference frames into a serving
    recurrence: rows with ``mask`` True take the new render, every other
    row keeps the running cross-tick reference BITWISE (``jnp.where`` is
    an elementwise lane select — unselected rows pass through untouched).

    This is the serving engine's slot-reuse leak-proofing primitive: a
    newly admitted session's recurrence row is fully overwritten by its
    own primed reference before any warp reads it, and continuing
    sessions' co-rendered references are never re-rendered (which would
    perturb their exclusive-run parity). ``mask`` [S] bool, ``rgb``
    [S, H, W, 3], ``dep`` [S, H, W].
    """
    m = mask[:, None, None]
    return (jnp.where(m[..., None], rgb_new, rgb_ref),
            jnp.where(m, dep_new, dep_ref))


# ---------------------------------------------------------------------------
# session sharding (ShardConfig -> jax.sharding)
# ---------------------------------------------------------------------------


def make_mesh(shard: Optional[ShardConfig]):
    """Build the 1-D session mesh for ``shard``, or None when disabled.

    Raises if the host exposes fewer devices than ``shard.num_devices`` —
    silently falling back would hide a misconfigured fleet.
    """
    if shard is None or not shard.enabled:
        return None
    devices = jax.devices()
    if len(devices) < shard.num_devices:
        raise ValueError(
            f"ShardConfig requests {shard.num_devices} devices but only "
            f"{len(devices)} are visible (JAX_PLATFORMS/XLA_FLAGS)")
    return jax.sharding.Mesh(np.asarray(devices[:shard.num_devices]),
                             (shard.axis_name,))


def session_sharding(mesh) -> jax.sharding.NamedSharding:
    """Sharding that splits the *leading* (session) axis across the mesh;
    trailing axes are replicated/unsplit."""
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(mesh.axis_names[0]))


def replicated_sharding(mesh) -> jax.sharding.NamedSharding:
    """Fully-replicated layout (model params, MVoxel table: one logical
    copy serves every session on every device)."""
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def shard_session_inputs(mesh, *arrays):
    """Lay the session sharding over each array's leading axis (device_put
    is device-to-device after the first tick — no host round-trip)."""
    sh = session_sharding(mesh)
    return tuple(jax.device_put(a, sh) for a in arrays)
