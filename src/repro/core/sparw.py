"""SPARW — Sparse Radiance Warping (paper §III).

Steps (Fig. 10): ① frame → point cloud (Eq. 1), ② rigid transform to the
target camera (Eq. 2), ③ perspective re-projection with z-buffering (Eq. 3),
④ sparse NeRF rendering of disoccluded pixels (Eq. 4).

All steps are pure JAX and jit-able; the z-buffer uses a deterministic
two-pass scatter-min (depth, then winner-index) so results are reproducible.
Void pixels: the volume renderer assigns background rays depth = far, so the
background warps like a skybox and passes the paper's depth test (§III-B ④)
instead of being re-rendered.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nerf.rays import Camera

# The rigid transforms run at float32 precision: a TPU's default for a
# float32 matmul is one bfloat16 pass, whose ~3e-3 relative error moves
# reprojected pixels and opens spurious holes.
_HIGHEST = jax.lax.Precision.HIGHEST


class WarpResult(NamedTuple):
    rgb: jnp.ndarray  # [H, W, 3] warped colors (holes = 0)
    depth: jnp.ndarray  # [H, W]  warped z-buffer depth (holes = +inf)
    holes: jnp.ndarray  # [H, W]  bool — needs sparse NeRF rendering
    warp_angle: jnp.ndarray  # [H, W] radians (only where warped)


def frame_to_pointcloud(depth: jnp.ndarray, cam: Camera) -> jnp.ndarray:
    """Eq. 1: per-pixel 3D points in the *reference camera* frame. [H*W, 3]."""
    h, w = depth.shape
    v, u = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                        jnp.arange(w, dtype=jnp.float32), indexing="ij")
    d = depth.reshape(-1)
    x = (u.reshape(-1) + 0.5 - cam.cx) * d / cam.focal
    y = (v.reshape(-1) + 0.5 - cam.cy) * d / cam.focal
    return jnp.stack([x, y, d], axis=-1)


def transform_points(points: jnp.ndarray, c2w_ref: jnp.ndarray,
                     c2w_tgt: jnp.ndarray) -> jnp.ndarray:
    """Eq. 2: T_{ref->tgt} = w2c_tgt @ c2w_ref applied to ref-frame points."""
    r_ref, t_ref = c2w_ref[:3, :3], c2w_ref[:3, 3]
    r_tgt, t_tgt = c2w_tgt[:3, :3], c2w_tgt[:3, 3]
    world = jnp.matmul(points, r_ref.T, precision=_HIGHEST) + t_ref
    # R^T x == x @ R
    return jnp.matmul(world - t_tgt, r_tgt, precision=_HIGHEST)


def project(points_tgt: jnp.ndarray, cam: Camera
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Eq. 3: perspective projection -> (u, v, z) in the target image."""
    z = points_tgt[:, 2]
    safe_z = jnp.where(jnp.abs(z) < 1e-6, 1e-6, z)
    u = cam.focal * points_tgt[:, 0] / safe_z + cam.cx - 0.5
    v = cam.focal * points_tgt[:, 1] / safe_z + cam.cy - 0.5
    return u, v, z


def _project_to_target(
    depth_ref: jnp.ndarray,  # [H, W]
    c2w_ref: jnp.ndarray,
    c2w_tgt: jnp.ndarray,
    cam: Camera,
    phi_deg: Optional[float],
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Steps ①–③ up to (but excluding) the z-buffer scatter.

    Returns per reference pixel: (target raster address [HW] int32,
    depth-in-target z [HW], valid [HW] bool, warp angle [HW]). Shared by
    the per-frame :func:`warp_frame` and the flat-batch
    :func:`warp_frames_flat` so both paths compute bit-identical geometry —
    only the scatter address space differs.
    """
    h, w = depth_ref.shape
    pts_ref = frame_to_pointcloud(depth_ref, cam)
    # world-space points computed once: reused for the Eq. 2 transform below
    # and for the warp-angle heuristic (transform_points would recompute it)
    world = (jnp.matmul(pts_ref, c2w_ref[:3, :3].T, precision=_HIGHEST)
             + c2w_ref[:3, 3])
    pts_tgt = jnp.matmul(world - c2w_tgt[:3, 3], c2w_tgt[:3, :3],
                         precision=_HIGHEST)  # R^T x == x @ R
    u, v, z = project(pts_tgt, cam)

    ui = jnp.round(u).astype(jnp.int32)
    vi = jnp.round(v).astype(jnp.int32)
    valid = (z > 1e-4) & (ui >= 0) & (ui < w) & (vi >= 0) & (vi < h)

    # Warp-angle heuristic (§III-C / Fig. 26): angle subtended at the scene
    # point between the reference ray and the target ray.
    ray_ref = world - c2w_ref[:3, 3]
    ray_tgt = world - c2w_tgt[:3, 3]
    cos = jnp.sum(ray_ref * ray_tgt, -1) / (
        jnp.linalg.norm(ray_ref, axis=-1) * jnp.linalg.norm(ray_tgt, axis=-1) + 1e-9)
    angle = jnp.arccos(jnp.clip(cos, -1.0, 1.0))
    if phi_deg is not None:
        valid = valid & (angle <= jnp.deg2rad(phi_deg))
    return vi * w + ui, z, valid, angle


def warp_frame(
    rgb_ref: jnp.ndarray,  # [H, W, 3]
    depth_ref: jnp.ndarray,  # [H, W]
    c2w_ref: jnp.ndarray,
    c2w_tgt: jnp.ndarray,
    cam: Camera,
    phi_deg: Optional[float] = None,
    depth_eps: float = 1e-3,
) -> WarpResult:
    """Warp a reference frame into the target camera (steps ①–③)."""
    h, w = depth_ref.shape
    n = h * w
    raster, z, valid, angle = _project_to_target(depth_ref, c2w_ref, c2w_tgt,
                                                 cam, phi_deg)
    flat = jnp.where(valid, raster, n)  # invalid -> dump slot n

    # pass 1: scatter-min depth
    zbuf = jnp.full((n + 1,), jnp.inf).at[flat].min(z)
    # pass 2: deterministic winner = max point-index among depth-ties
    is_front = valid & (z <= zbuf[flat] + depth_eps)
    idx = jnp.arange(n, dtype=jnp.int32)
    winner = jnp.full((n + 1,), -1, jnp.int32).at[
        jnp.where(is_front, flat, n)].max(idx)

    src = winner[:n]  # for each target pixel: source point index or -1
    has = src >= 0
    src_c = jnp.maximum(src, 0)
    rgb = jnp.where(has[:, None], rgb_ref.reshape(-1, 3)[src_c], 0.0)
    depth = jnp.where(has, zbuf[:n], jnp.inf)
    ang = jnp.where(has, angle[src_c], 0.0)
    return WarpResult(
        rgb=rgb.reshape(h, w, 3),
        depth=depth.reshape(h, w),
        holes=~has.reshape(h, w),
        warp_angle=ang.reshape(h, w),
    )


@jax.named_scope("warp")
def warp_frames_flat(
    rgb_ref: jnp.ndarray,  # [S, H, W, 3] per-session reference frames
    depth_ref: jnp.ndarray,  # [S, H, W]
    c2w_ref: jnp.ndarray,  # [S, 4, 4]
    c2w_tgt: jnp.ndarray,  # [S, N, 4, 4]
    cam: Camera,
    phi_deg: Optional[float] = None,
    depth_eps: float = 1e-3,
) -> WarpResult:
    """Warp every session's window in ONE flat scatter pass.

    The projection geometry is the vmapped :func:`_project_to_target`
    (bit-identical per element to the per-frame path); the z-buffer and
    winner resolution then run as single scatters over a flat
    ``[S * N * H * W]`` address space instead of ``S × N`` small vmapped
    scatters — the irregular-work regularization the flat ray-batch core
    exists for. Segment addresses are ``(session, frame)``-major, so no
    two frames' candidates ever collide and (under session sharding) a
    scatter never crosses a device boundary.

    Returns a :class:`WarpResult` whose fields carry leading ``[S, N]``
    axes. Each ``[s, n]`` slice is bit-identical to
    ``warp_frame(rgb_ref[s], depth_ref[s], c2w_ref[s], c2w_tgt[s, n])``.
    """
    s, n = c2w_tgt.shape[0], c2w_tgt.shape[1]
    h, w = depth_ref.shape[-2:]
    hw = h * w
    b = s * n  # total frames in the tick
    proj = jax.vmap(  # over sessions ...
        jax.vmap(_project_to_target, in_axes=(None, None, 0, None, None)),
        in_axes=(0, 0, 0, None, None),
    )  # ... and over each session's window
    raster, z, valid, angle = proj(depth_ref, c2w_ref, c2w_tgt, cam, phi_deg)
    # [S, N, HW] -> flat [B * HW] with (session, frame)-major addresses;
    # invalid candidates go out of range and are dropped by mode="drop"
    seg_off = (jnp.arange(b, dtype=jnp.int32) * hw).reshape(s, n, 1)
    flat = jnp.where(valid, seg_off + raster, b * hw).reshape(-1)
    z_flat = z.reshape(-1)

    # pass 1: ONE scatter-min depth over every frame of every session
    zbuf = jnp.full((b * hw,), jnp.inf).at[flat].min(z_flat, mode="drop")
    # pass 2: deterministic winner = max source-point index among ties.
    # The point index is globally offset per session (i + s*HW) so one flat
    # gather pulls the winning color from the packed reference frames; the
    # per-pixel winner is unchanged (all of a pixel's candidates share s).
    zb_at = zbuf[jnp.minimum(flat, b * hw - 1)]
    is_front = valid.reshape(-1) & (z_flat <= zb_at + depth_eps)
    pid = (jnp.arange(hw, dtype=jnp.int32)[None, :]
           + (jnp.arange(s, dtype=jnp.int32) * hw)[:, None])  # [S, HW]
    pid = jnp.broadcast_to(pid[:, None, :], (s, n, hw)).reshape(-1)
    winner = jnp.full((b * hw,), -1, jnp.int32).at[
        jnp.where(is_front, flat, b * hw)].max(pid, mode="drop")

    has = winner >= 0
    src_global = jnp.maximum(winner, 0)  # index into [S*HW] packed refs
    rgb = jnp.where(has[:, None], rgb_ref.reshape(-1, 3)[src_global], 0.0)
    depth = jnp.where(has, zbuf, jnp.inf)
    # the warp angle lives on the (source point, target frame) pair: gather
    # it per output frame from that frame's own angle row
    ang_rows = jnp.take_along_axis(angle.reshape(b, hw),
                                   src_global.reshape(b, hw) % hw, axis=1)
    ang = jnp.where(has, ang_rows.reshape(-1), 0.0)
    return WarpResult(
        rgb=rgb.reshape(s, n, h, w, 3),
        depth=depth.reshape(s, n, h, w),
        holes=~has.reshape(s, n, h, w),
        warp_angle=ang.reshape(s, n, h, w),
    )


def combine(warped: WarpResult, sparse_rgb: jnp.ndarray, holes: jnp.ndarray
            ) -> jnp.ndarray:
    """Eq. 4: F_tgt = F'_tgt ⊛ Γ_sp — fill holes with sparse NeRF output."""
    return jnp.where(holes[..., None], sparse_rgb, warped.rgb)


# ---------------------------------------------------------------------------
# fixed-capacity hole compaction (step ④ staging)
# ---------------------------------------------------------------------------


def compact_holes(hflat: jnp.ndarray, cap: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[HW] bool -> ([cap] hole pixel ids in raster order, true count).

    Deterministic cumsum-scatter compaction (the in-graph replacement for
    host ``np.nonzero``). Slots past the hole count alias pixel 0; they
    are masked out when scattering rendered colors back.
    """
    n = hflat.shape[0]
    pos = jnp.cumsum(hflat) - 1  # rank among holes
    slot = jnp.where(hflat & (pos < cap), pos, cap)
    idx = jnp.zeros((cap + 1,), jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return idx[:cap], hflat.sum()


@jax.named_scope("compact")
def compact_holes_flat(holes: jnp.ndarray, cap: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compact every (session, frame)'s holes in ONE flat scatter.

    ``holes`` is ``[S, N, HW]`` bool; returns (``idx [S, N, cap]`` hole
    pixel ids in raster order, ``counts [S, N]`` true hole counts). The
    compaction slots are emitted as *flat segment offsets* — segment
    ``(s, n)`` owns rows ``[(s*N + n) * (cap+1), ...)`` of one scatter
    address space — so the whole tick compacts with a single scatter
    instead of S×N vmapped ones. Each ``[s, n]`` slice is bit-identical
    to :func:`compact_holes` on that frame.
    """
    s, n, hw = holes.shape
    b = s * n
    hf = holes.reshape(b, hw)
    pos = jnp.cumsum(hf, axis=1) - 1  # rank among the frame's holes
    slot = jnp.where(hf & (pos < cap), pos, cap)  # [B, HW] in [0, cap]
    seg_off = jnp.arange(b, dtype=jnp.int32)[:, None] * (cap + 1)
    pix = jnp.broadcast_to(jnp.arange(hw, dtype=jnp.int32), (b, hw))
    idx = jnp.zeros((b * (cap + 1),), jnp.int32).at[
        (seg_off + slot).reshape(-1)].set(pix.reshape(-1), mode="drop")
    idx = idx.reshape(b, cap + 1)[:, :cap]  # drop each segment's dump slot
    return idx.reshape(s, n, cap), hf.sum(axis=1).reshape(s, n)


@jax.named_scope("compact")
def compact_holes_pooled(holes: jnp.ndarray, bucket: int,
                         live: Optional[jnp.ndarray] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compact a whole session window's holes into ONE pooled region.

    ``holes`` is ``[S, N, HW]`` bool. Where :func:`compact_holes_flat`
    reserves worst-case ``cap`` rows per *frame* (``S*N*cap`` total), the
    pooled compaction reserves one ``[bucket]`` region per *session*: all
    of a session's live frames compact contiguously, in (frame-major,
    raster) order, into rows ``[s*bucket, (s+1)*bucket)`` of the tick's
    flat hole batch. Returns (``addr [S, bucket]`` frame-local sample
    addresses ``n*HW + pixel`` in emission order, ``totals [S]`` true
    live-window hole totals). Rows past a session's total alias address 0
    (frame 0, pixel 0) and are masked at scatter time, exactly like the
    per-frame compaction's dump-slot discipline.

    ``live`` ``[S, N]`` masks padded frames (ragged windows) out of the
    pool — they must not consume capacity or shift their session's sample
    addresses relative to an exclusive run without pads. Whenever
    ``bucket >= totals[s]``, session ``s``'s address list is exactly the
    concatenation of the per-frame :func:`compact_holes_flat` lists
    (offset by ``n*HW``) — property-tested in ``tests/test_raybatch.py``.
    """
    s, n, hw = holes.shape
    if live is not None:
        holes = holes & live[:, :, None]
    hf = holes.reshape(s, n * hw)
    pos = jnp.cumsum(hf, axis=1) - 1  # rank among the session's holes
    slot = jnp.where(hf & (pos < bucket), pos, bucket)  # [S, N*HW]
    seg_off = jnp.arange(s, dtype=jnp.int32)[:, None] * (bucket + 1)
    local = jnp.broadcast_to(jnp.arange(n * hw, dtype=jnp.int32), (s, n * hw))
    addr = jnp.zeros((s * (bucket + 1),), jnp.int32).at[
        (seg_off + slot).reshape(-1)].set(local.reshape(-1), mode="drop")
    addr = addr.reshape(s, bucket + 1)[:, :bucket]  # drop the dump slot
    return addr, hf.sum(axis=1)


def warp_disagreement(rgb: jnp.ndarray, holes: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Warped-neighborhood radiance disagreement (ASDR's sampling signal).

    ``rgb`` ``[..., H, W, 3]`` warped colors, ``holes`` ``[..., H, W]``.
    For every pixel, computes the variance of the *warped* (non-hole)
    colors in its 3x3 neighborhood, averaged over channels, plus the count
    of warped neighbors. A hole surrounded by many low-variance warped
    pixels sits on radiance the warp already agrees about — a coarse
    sample budget suffices; few neighbors or high variance mark
    disocclusion edges that keep the full budget.
    """
    h, w = holes.shape[-2:]
    wgt = (~holes).astype(rgb.dtype)[..., None]  # [..., H, W, 1]

    def box3(a):  # 3x3 neighborhood sum with zero padding over H, W
        pad = [(0, 0)] * (a.ndim - 3) + [(1, 1), (1, 1), (0, 0)]
        p = jnp.pad(a, pad)
        return sum(p[..., i:i + h, j:j + w, :]
                   for i in range(3) for j in range(3))

    cnt = box3(wgt)                      # [..., H, W, 1]
    s1 = box3(rgb * wgt)
    s2 = box3(rgb * rgb * wgt)
    denom = jnp.maximum(cnt, 1.0)
    mean = s1 / denom
    var = jnp.maximum(s2 / denom - mean * mean, 0.0).mean(axis=-1)
    return var, cnt[..., 0].astype(jnp.int32)


def hole_fraction(holes: jnp.ndarray) -> jnp.ndarray:
    return holes.mean()
