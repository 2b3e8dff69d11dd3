"""The benchmark's own scene, shared by every architecture.

The scene is the analytic sphere-and-ground field of the named scene
(seeded from the CRC32 of its name, as the repository's procedural scenes
are). An architecture module (``bench/arch/<arch>.py``) stores it in its
model's layout, on the device in one jitted call from the configuration
and the seed; nothing is taken from the program under test, and the plain
reference reads those same arrays.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

LIGHT = np.array([0.35, 0.8, 0.49])
GROUND = -0.55
GROUND_ALBEDO = (0.65, 0.62, 0.58)
SHARPNESS = 40.0
DENSITY_SCALE = 60.0


def scene_spheres(name: str, num_spheres: int = 6):
    """(centers [K,3], radii [K], albedos [K,3]) of the named scene."""
    rng = np.random.default_rng(zlib.crc32(name.encode("utf-8")))
    centers = rng.uniform(-0.55, 0.55, size=(num_spheres, 3))
    centers[:, 1] = rng.uniform(-0.35, 0.45, size=num_spheres)
    radii = rng.uniform(0.12, 0.3, size=num_spheres)
    albedos = rng.uniform(0.15, 0.95, size=(num_spheres, 3))
    return (centers.astype(np.float32), radii.astype(np.float32),
            albedos.astype(np.float32))


def scene_field(p, centers, radii, albedos):
    """``(sigma [P], rgb [P, 3])`` of the scene at the points ``p [P, 3]``:
    a density that rises across each surface, Lambert-shaded albedo with a
    faint texture, nothing outside the unit cube."""
    d_sph = (jnp.linalg.norm(p[:, None, :] - centers[None], axis=-1)
             - radii[None])
    d_all = jnp.concatenate([d_sph, (p[:, 1] - GROUND)[:, None]], axis=1)
    idx = jnp.argmin(d_all, axis=1)
    d = jnp.min(d_all, axis=1)
    inside = jnp.all(jnp.abs(p) <= 1.0, axis=-1)
    sigma = jnp.where(inside, DENSITY_SCALE * jax.nn.sigmoid(-SHARPNESS * d),
                      0.0)
    n_sph = p[:, None, :] - centers[None]
    n_sph = n_sph / (jnp.linalg.norm(n_sph, axis=-1, keepdims=True) + 1e-9)
    n_gnd = jnp.broadcast_to(jnp.array([0.0, 1.0, 0.0]), p.shape)[:, None, :]
    normals = jnp.concatenate([n_sph, n_gnd], axis=1)
    n = jnp.take_along_axis(normals, idx[:, None, None], axis=1).squeeze(1)
    albs = jnp.concatenate([albedos, jnp.array([GROUND_ALBEDO])], axis=0)
    light = jnp.asarray(LIGHT / np.linalg.norm(LIGHT), jnp.float32)
    lambert = 0.35 + 0.65 * jnp.clip((n * light).sum(-1, keepdims=True),
                                     0.0, 1.0)
    tex = 0.9 + 0.1 * jnp.sin(9.0 * p[:, :1]) * jnp.cos(7.0 * p[:, 2:3])
    rgb = jnp.clip(albs[idx] * lambert * tex, 0.0, 1.0)
    return sigma, rgb


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, wider than 32 bits too."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
