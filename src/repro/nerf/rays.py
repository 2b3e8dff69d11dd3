"""Cameras, ray generation and ray-sample generation (Indexing stage ``I``).

Conventions: OpenCV-style pinhole camera. ``c2w`` is a 4x4 camera-to-world
matrix; camera looks down +Z in camera space; image (v, u) = (row, col).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Camera:
    """Pinhole intrinsics (Eq. 1/3 of the paper use f, cx, cy)."""

    height: int
    width: int
    focal: float
    cx: float
    cy: float

    @staticmethod
    def square(res: int, fov_deg: float = 50.0) -> "Camera":
        focal = 0.5 * res / jnp.tan(jnp.deg2rad(fov_deg) / 2.0)
        return Camera(height=res, width=res, focal=float(focal), cx=res / 2.0, cy=res / 2.0)


def look_at(eye: jnp.ndarray, target: jnp.ndarray, up=None) -> jnp.ndarray:
    """Build a c2w pose with camera at ``eye`` looking at ``target``."""
    if up is None:
        up = jnp.array([0.0, 1.0, 0.0])
    fwd = target - eye
    fwd = fwd / (jnp.linalg.norm(fwd) + 1e-9)
    right = jnp.cross(fwd, up)
    right = right / (jnp.linalg.norm(right) + 1e-9)
    down = jnp.cross(fwd, right)
    c2w = jnp.eye(4)
    # camera axes: x=right, y=down(image v), z=forward
    c2w = c2w.at[:3, 0].set(right).at[:3, 1].set(down).at[:3, 2].set(fwd)
    c2w = c2w.at[:3, 3].set(eye)
    return c2w


def orbit_pose(t: jnp.ndarray, radius: float = 2.6, height: float = 0.9,
               target=None, wobble: float = 0.0) -> jnp.ndarray:
    """Camera orbiting the origin; ``t`` in radians. Used for trajectories."""
    if target is None:
        target = jnp.zeros(3)
    eye = jnp.array([
        radius * jnp.cos(t),
        height + wobble * jnp.sin(3.0 * t),
        radius * jnp.sin(t),
    ])
    return look_at(eye, target)


@functools.lru_cache(maxsize=None)
def camera_dirs(cam: Camera) -> np.ndarray:
    """Camera-space per-pixel ray directions [H*W, 3] (row-major).

    Pose-independent, so it is computed once per camera (a host-side numpy
    constant — cache-safe under tracing); inside a jitted trace it folds to
    a constant instead of re-deriving the pixel grid for every pose of a
    batched warp window.
    """
    v, u = np.meshgrid(
        np.arange(cam.height, dtype=np.float32),
        np.arange(cam.width, dtype=np.float32),
        indexing="ij",
    )
    x = (u + 0.5 - cam.cx) / cam.focal
    y = (v + 0.5 - cam.cy) / cam.focal
    return np.stack([x, y, np.ones_like(x)], axis=-1).reshape(-1, 3)


@functools.lru_cache(maxsize=None)
def camera_dirs_device(cam: Camera) -> jnp.ndarray:
    """Device-resident :func:`camera_dirs` — uploaded ONCE per camera per
    process, outside any trace. Converting the numpy constant inside a
    jitted body instead would bake a ``device_put`` into every traced tick
    program (re-uploading the pixel grid per compile — flagged by
    ``repro.analysis``'s jaxpr-device-put rule). ``ensure_compile_time_eval``
    keeps the upload out of the trace even when the cache is first warmed
    from inside a jitted body."""
    with jax.ensure_compile_time_eval():
        return jnp.asarray(camera_dirs(cam))


def generate_rays(cam: Camera, c2w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-pixel ray origins/directions in world space.

    Returns (origins [H*W, 3], directions [H*W, 3]); directions are unit-norm.
    Row-major pixel order — the *pixel-centric* order the paper starts from.
    """
    # float32 precision: a TPU's default matmul is one bfloat16 pass
    dirs_world = jnp.matmul(camera_dirs_device(cam), c2w[:3, :3].T,
                            precision=jax.lax.Precision.HIGHEST)
    dirs_world = dirs_world / jnp.linalg.norm(dirs_world, axis=-1, keepdims=True)
    origins = jnp.broadcast_to(c2w[:3, 3], dirs_world.shape)
    return origins, dirs_world


def generate_rays_batch(cam: Camera, c2ws: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rays for a whole pose batch [N,4,4] -> ([N,H*W,3], [N,H*W,3])."""
    return jax.vmap(lambda p: generate_rays(cam, p))(c2ws)


@jax.named_scope("compact")
def sample_along_rays(
    origins: jnp.ndarray,
    dirs: jnp.ndarray,
    near: float,
    far: float,
    num_samples: int,
    key: jax.Array | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stratified samples along each ray.

    Returns (points [R, N, 3], t_vals [R, N]).
    """
    r = origins.shape[0]
    t = jnp.linspace(near, far, num_samples, dtype=jnp.float32)
    t = jnp.broadcast_to(t, (r, num_samples))
    if key is not None:
        delta = (far - near) / num_samples
        t = t + jax.random.uniform(key, t.shape, minval=0.0, maxval=delta)
    points = origins[:, None, :] + dirs[:, None, :] * t[..., None]
    return points, t
