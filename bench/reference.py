"""Plain reference of the frames a SpaRW serving tick delivers.

Written from the method (Cicero, arXiv 2404.11852, section III) and the
serving contract, in straightforward ``jax.numpy``; it imports nothing of
the program under test and reads only the benchmark's own weights and
poses. The model's field (its density and colour at a point seen from a
direction) is the architecture's, ``field`` of ``bench/arch/<arch>.py``;
the rest is shared. For one session window of ``N`` target poses:

1. the reference pose: the window's first pose for a session's first
   window, otherwise extrapolated from the session's last two poses
   ``N/2`` frame intervals ahead (rotation on SO(3) by log/exp,
   translation linearly);
2. the reference frame: every pixel's ray rendered by the volume
   renderer (uniform samples between ``near`` and ``far``, the field at
   each, alpha compositing over a white background, depth as the weighted
   sample distance plus the far plane for the transmitted remainder);
3. each target frame: the reference frame unprojected by its depth,
   moved into the target camera, rounded to the nearest pixel and
   z-buffered (the nearest depth wins; among candidates within 1e-3 of
   it, the highest source index); pixels that receive no point are holes;
   a hole is *settled* when no point comes within ``SETTLE_PX`` of a
   pixel's rounding edge towards it, so that rounding alone cannot fill
   it;
4. holes rendered as in step 2 from the target pose.

Every contraction goes through :func:`contract` (an elementwise product
that a field sums, such as a trilinear corner sum, through
:func:`multiply`), whose ``precision`` is
``"highest"`` (float32) for the reference or ``"high"`` (three bfloat16
passes, emulated so that every platform computes the same thing) for the
benchmark's control.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK_RAYS = 4096
DEPTH_EPS = 1e-3
# a hole that a point projected within this many pixels of it could fill
# is not settled
SETTLE_PX = 0.02


def _to_bf16(x):
    """``x`` rounded to bfloat16's 8-bit mantissa, kept in float32. An
    explicit rounding: a TPU compiler that may keep excess precision can
    drop a float32 -> bfloat16 -> float32 round trip, and would then split
    ``x`` into ``(x, 0)``."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split_bf16(x):
    hi = _to_bf16(x)
    return hi, _to_bf16(x - hi)


def multiply(a, b, precision: str):
    """Elementwise ``a * b`` at float32 or at three bfloat16 passes."""
    if precision == "highest":
        return a * b
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return ah * bh + (ah * bl + al * bh)


def contract(spec: str, a, b, precision: str):
    """``jnp.einsum(spec, a, b)`` at float32 (``"highest"``) or at three
    bfloat16 passes (``"high"``: hi*hi + hi*lo + lo*hi, each product exact
    in float32)."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)

    def e(x, y):
        return jnp.einsum(spec, x, y, precision=HIGHEST)

    return e(ah, bh) + (e(ah, bl) + e(al, bh))


class Camera:
    """Square pinhole camera: ``res`` pixels, ``fov_deg`` field of view,
    OpenCV axes (x right, y down, z forward)."""

    def __init__(self, res: int, fov_deg: float):
        self.res = res
        half = np.float32(np.deg2rad(np.float32(fov_deg))) / np.float32(2.0)
        self.focal = float(np.float32(0.5 * res) / np.tan(half))
        self.c = res / 2.0
        v, u = np.meshgrid(np.arange(res, dtype=np.float32),
                           np.arange(res, dtype=np.float32), indexing="ij")
        self.u = ((u + 0.5 - self.c) / self.focal).reshape(-1)
        self.v = ((v + 0.5 - self.c) / self.focal).reshape(-1)


# ---------------------------------------------------------------------------
# poses
# ---------------------------------------------------------------------------


def _so3_log(r):
    cos = jnp.clip((jnp.trace(r) - 1.0) / 2.0, -1.0, 1.0)
    theta = jnp.arccos(cos)
    w = jnp.array([r[2, 1] - r[1, 2], r[0, 2] - r[2, 0], r[1, 0] - r[0, 1]])
    scale = jnp.where(theta < 1e-6, 0.5,
                      theta / (2.0 * jnp.sin(theta) + 1e-12))
    return w * scale


def _so3_exp(w, precision):
    theta = jnp.linalg.norm(w)
    k = w / (theta + 1e-12)
    kx = jnp.array([[0.0, -k[2], k[1]], [k[2], 0.0, -k[0]],
                    [-k[1], k[0], 0.0]])
    r = (jnp.eye(3) + jnp.sin(theta) * kx
         + (1.0 - jnp.cos(theta)) * contract("ij,jk->ik", kx, kx, precision))
    return jnp.where(theta < 1e-8, jnp.eye(3), r)


@partial(jax.jit, static_argnames=("precision",))
def extrapolate(prev, curr, steps, *, precision):
    t = curr[:3, 3] + (curr[:3, 3] - prev[:3, 3]) * steps
    dr = contract("ij,kj->ik", curr[:3, :3], prev[:3, :3], precision)
    w = _so3_log(dr)
    r = contract("ij,jk->ik", _so3_exp(w * steps, precision), curr[:3, :3],
                 precision)
    return jnp.eye(4).at[:3, :3].set(r).at[:3, 3].set(t)


def reference_pose(poses: Sequence[np.ndarray], start: int, window: int,
                   precision: str):
    """Pose of the reference frame that serves frames ``start ..
    start + window - 1`` of a session."""
    if start == 0:
        return jnp.asarray(poses[0], jnp.float32)
    return extrapolate(jnp.asarray(poses[start - 2], jnp.float32),
                       jnp.asarray(poses[start - 1], jnp.float32),
                       jnp.float32(window / 2.0), precision=precision)


# ---------------------------------------------------------------------------
# volume rendering
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("field", "key", "samples", "precision"))
def render_rays(weights, origins, dirs, *, field, key, samples, precision):
    """Colors ``[R, 3]`` and depths ``[R]`` of the rays ``origins``/``dirs``
    through the architecture's ``field`` (its static part ``key``).
    ``samples`` is ``(num_samples, near, far)``."""
    ns, near, far = samples
    t = jnp.broadcast_to(jnp.linspace(near, far, ns, dtype=jnp.float32),
                         (origins.shape[0], ns))
    pts = origins[:, None, :] + dirs[:, None, :] * t[..., None]
    sigma, rgb = field(weights, pts.reshape(-1, 3),
                       jnp.repeat(dirs, ns, axis=0), key, precision)
    sigma, rgb = sigma.reshape(-1, ns), rgb.reshape(-1, ns, 3)
    delta = jnp.diff(t, axis=-1)
    delta = jnp.concatenate([delta, delta[:, -1:]], axis=-1)
    alpha = 1.0 - jnp.exp(-jnp.maximum(sigma, 0.0) * delta)
    trans = jnp.cumprod(1.0 - alpha + 1e-10, axis=-1)
    trans = jnp.concatenate([jnp.ones_like(trans[:, :1]), trans[:, :-1]], -1)
    w = trans * alpha
    acc = w.sum(axis=-1)
    color = contract("rn,rnc->rc", w, rgb, precision) + (1.0 - acc)[:, None]
    depth = contract("rn,rn->r", w, t, precision) + (1.0 - acc) * far
    return color, depth


@partial(jax.jit, static_argnames=("precision",))
def frame_rays(u, v, pose, *, precision):
    """Every pixel's ray of the frame at ``pose``: (origins, directions),
    each ``[HW, 3]``."""
    d_cam = jnp.stack([u, v, jnp.ones_like(u)], -1)
    d = contract("pk,jk->pj", d_cam, pose[:3, :3], precision)
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    return jnp.broadcast_to(pose[:3, 3], d.shape), d


def render(weights, origins: np.ndarray, dirs: np.ndarray, model: dict,
           precision: str, chunk: int) -> Tuple[np.ndarray, np.ndarray]:
    """Render rays in chunks of ``chunk`` (the last one padded), so that
    every call has one shape. ``model`` holds :func:`render_rays`'s static
    arguments but ``precision``."""
    n = len(origins)
    if n == 0:
        return np.zeros((0, 3), np.float32), np.zeros((0,), np.float32)
    pad = (-n) % chunk
    o = np.concatenate([origins, np.zeros((pad, 3), np.float32)])
    d = np.concatenate([dirs, np.tile(np.float32([[0, 0, 1]]), (pad, 1))])
    cols, deps = [], []
    for i in range(0, len(o), chunk):
        col, dep = render_rays(weights, jnp.asarray(o[i:i + chunk]),
                               jnp.asarray(d[i:i + chunk]), **model,
                               precision=precision)
        cols.append(np.asarray(col))
        deps.append(np.asarray(dep))
    return np.concatenate(cols)[:n], np.concatenate(deps)[:n]


def pixel_rays(cam: Camera, pose, precision: str):
    o, d = frame_rays(jnp.asarray(cam.u), jnp.asarray(cam.v), pose,
                      precision=precision)
    return np.asarray(o), np.asarray(d)


# ---------------------------------------------------------------------------
# warping
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("res", "focal", "precision"))
def warp(rgb_ref, dep_ref, pose_ref, pose_tgt, *, res, focal, precision):
    """Warp a reference frame (``[HW, 3]``, ``[HW]``) into the target
    camera: (warped colors ``[HW, 3]``, hole mask ``[HW]``, settled-hole
    mask ``[HW]``)."""
    n = res * res
    c = res / 2.0
    v, u = jnp.meshgrid(jnp.arange(res, dtype=jnp.float32),
                        jnp.arange(res, dtype=jnp.float32), indexing="ij")
    d = dep_ref
    pts = jnp.stack([(u.reshape(-1) + 0.5 - c) * d / focal,
                     (v.reshape(-1) + 0.5 - c) * d / focal, d], -1)
    world = contract("pk,jk->pj", pts, pose_ref[:3, :3], precision) \
        + pose_ref[:3, 3]
    tgt = contract("pk,kj->pj", world - pose_tgt[:3, 3], pose_tgt[:3, :3],
                   precision)
    z = tgt[:, 2]
    safe = jnp.where(jnp.abs(z) < 1e-6, 1e-6, z)
    fu = focal * tgt[:, 0] / safe + c - 0.5
    fv = focal * tgt[:, 1] / safe + c - 0.5
    ui = jnp.round(fu).astype(jnp.int32)
    vi = jnp.round(fv).astype(jnp.int32)
    ahead = z > 1e-4
    ok = ahead & (ui >= 0) & (ui < res) & (vi >= 0) & (vi < res)
    dest = jnp.where(ok, vi * res + ui, n)
    zbuf = jnp.full((n + 1,), jnp.inf).at[dest].min(z)
    front = ok & (z <= zbuf[dest] + DEPTH_EPS)
    win = jnp.full((n + 1,), -1, jnp.int32).at[
        jnp.where(front, dest, n)].max(jnp.arange(n, dtype=jnp.int32))[:n]
    has = win >= 0
    rgb = jnp.where(has[:, None], rgb_ref[jnp.maximum(win, 0)], 0.0)
    # every pixel a point reaches with its projection moved by SETTLE_PX
    reach = jnp.zeros((n + 1,), bool)
    for du in (-SETTLE_PX, SETTLE_PX):
        for dv in (-SETTLE_PX, SETTLE_PX):
            uj = jnp.round(fu + du).astype(jnp.int32)
            vj = jnp.round(fv + dv).astype(jnp.int32)
            okj = ahead & (uj >= 0) & (uj < res) & (vj >= 0) & (vj < res)
            reach = reach.at[jnp.where(okj, vj * res + uj, n)].set(True)
    return rgb, ~has, ~has & ~reach[:n]


# ---------------------------------------------------------------------------
# one session window
# ---------------------------------------------------------------------------


def model_key(arch, cfg: dict) -> dict:
    """:func:`render_rays`'s static arguments for a model of the
    architecture ``arch`` (``bench/arch/<arch>.py``)."""
    return {"field": arch.field, "key": arch.reference_key(cfg),
            "samples": (cfg["num_samples"], float(cfg["near"]),
                        float(cfg["far"]))}


def render_window(arch, weights, cfg: dict, cam: Camera,
                  poses: List[np.ndarray], start: int, count: int,
                  precision: str) -> Dict[str, object]:
    """Reference frames of one session window of a model of the
    architecture ``arch``: frames ``start .. start + count - 1`` of a
    session whose poses are ``poses``. Returns the
    reference frame (``ref_rgb [HW, 3]``, ``ref_depth [HW]``), the target
    frames ``frames [count, HW, 3]``, their hole counts and each frame's
    settled hole pixels (``settled``, a list of pixel indices)."""
    model = model_key(arch, cfg)
    chunk = min(CHUNK_RAYS, cam.res * cam.res)
    pose_ref = reference_pose(poses, start, cfg["window"], precision)
    ref_rgb, ref_dep = render(weights, *pixel_rays(cam, pose_ref, precision),
                              model, precision, chunk)
    frames, holes, settled, hole_o, hole_d = [], [], [], [], []
    for f in range(start, start + count):
        pose_tgt = jnp.asarray(poses[f], jnp.float32)
        rgb, hole, settle = warp(jnp.asarray(ref_rgb), jnp.asarray(ref_dep),
                                 pose_ref, pose_tgt, res=cam.res,
                                 focal=cam.focal, precision=precision)
        pix = np.nonzero(np.asarray(hole))[0]
        o, d = pixel_rays(cam, pose_tgt, precision)
        frames.append(np.array(rgb))
        holes.append(pix)
        settled.append(np.nonzero(np.asarray(settle))[0])
        hole_o.append(o[pix])
        hole_d.append(d[pix])
    # the window's holes render together, as one batch of rays
    fill = render(weights, np.concatenate(hole_o), np.concatenate(hole_d),
                  model, precision, chunk)[0]
    at = 0
    for rgb, pix in zip(frames, holes):
        rgb[pix] = fill[at:at + len(pix)]
        at += len(pix)
    return {"ref_rgb": ref_rgb, "ref_depth": ref_dep,
            "frames": np.stack(frames),
            "hole_counts": [int(len(p)) for p in holes],
            "settled": settled}
