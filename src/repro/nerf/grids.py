"""Feature representations covering the paper's three model families.

* ``DenseGrid``   — DirectVoxGO-style dense voxel grid.
* ``HashGrid``    — Instant-NGP-style multiresolution hash encoding.
* ``TensoRFGrid`` — TensoRF-style factorized (VM) tensor.

Each representation exposes:
  ``init(key, cfg) -> params``
  ``query(params, points [S,3]) -> features [S,C]``           (pixel-centric path)
  ``corner_ids_weights(points) -> (ids [S,8], w [S,8], res)``  (what Feature
     Gathering needs: the 8 vertex ids + trilerp weights — the unit the paper's
     RIT/GU operates on; only meaningful for the voxel-vertex representations)

Scene domain is the cube [-1, 1]^3.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

# ----------------------------------------------------------------------------
# shared voxel-vertex math
# ----------------------------------------------------------------------------

_CORNERS = jnp.array(
    [[i, j, k] for i in (0, 1) for j in (0, 1) for k in (0, 1)], dtype=jnp.int32
)  # [8, 3]


def _to_grid_coords(points: jnp.ndarray, res: int) -> jnp.ndarray:
    """Map [-1,1]^3 -> [0, res-1] continuous grid coordinates."""
    x = (points + 1.0) * 0.5 * (res - 1)
    return jnp.clip(x, 0.0, res - 1 - 1e-4)


def corner_ids_weights(points: jnp.ndarray, res: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """8 corner vertex ids (flattened) + trilinear weights for each point.

    points: [S, 3] in [-1,1]^3  ->  ids [S, 8] int32, weights [S, 8] f32.
    Vertex id = x * res^2 + y * res + z (x-major: the DRAM layout order).
    """
    g = _to_grid_coords(points, res)
    base = jnp.floor(g).astype(jnp.int32)  # [S,3]
    frac = g - base  # [S,3]
    corners = base[:, None, :] + _CORNERS[None, :, :]  # [S,8,3]
    corners = jnp.clip(corners, 0, res - 1)
    ids = (corners[..., 0] * res + corners[..., 1]) * res + corners[..., 2]
    cw = jnp.where(_CORNERS[None, :, :] == 1, frac[:, None, :], 1.0 - frac[:, None, :])
    weights = cw.prod(axis=-1)  # [S,8]
    return ids, weights


def gather_trilerp_ref(table: jnp.ndarray, ids: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """Reference gather+interp: out[s] = sum_v w[s,v] * table[ids[s,v]]."""
    feats = table[ids]  # [S,8,C]
    return jnp.einsum("svc,sv->sc", feats, weights,
                      precision=jax.lax.Precision.HIGHEST)


# ----------------------------------------------------------------------------
# DenseGrid (DirectVoxGO)
# ----------------------------------------------------------------------------


@dataclass(frozen=True)
class DenseGridCfg:
    res: int = 64
    channels: int = 8


def dense_init(key: jax.Array, cfg: DenseGridCfg) -> dict:
    table = 0.01 * jax.random.normal(key, (cfg.res**3, cfg.channels), jnp.float32)
    return {"table": table}


def dense_query(params: dict, points: jnp.ndarray, cfg: DenseGridCfg) -> jnp.ndarray:
    ids, w = corner_ids_weights(points, cfg.res)
    return gather_trilerp_ref(params["table"], ids, w)


# ----------------------------------------------------------------------------
# HashGrid (Instant-NGP)
# ----------------------------------------------------------------------------

_PRIMES = jnp.array([1, 2654435761, 805459861], dtype=jnp.uint32)


@dataclass(frozen=True)
class HashGridCfg:
    num_levels: int = 8
    base_res: int = 16
    max_res: int = 256
    table_size: int = 2**14  # T per level
    channels: int = 2  # F per level

    @property
    def out_channels(self) -> int:
        return self.num_levels * self.channels

    def level_res(self, level: int) -> int:
        if self.num_levels == 1:
            return self.base_res
        b = (self.max_res / self.base_res) ** (1.0 / (self.num_levels - 1))
        return int(round(self.base_res * b**level))

    def level_dense(self, level: int) -> bool:
        """Low-res levels are stored dense (streamable); high-res levels hash.

        Mirrors the paper's observation that NGP levels >= ~5 revert to the
        non-streaming path.
        """
        res = self.level_res(level)
        return res**3 <= self.table_size


def _hash_coords(coords: jnp.ndarray, table_size: int) -> jnp.ndarray:
    """Spatial hash of integer coords [..., 3] -> [0, table_size)."""
    c = coords.astype(jnp.uint32) * _PRIMES
    h = c[..., 0] ^ c[..., 1] ^ c[..., 2]
    return (h % jnp.uint32(table_size)).astype(jnp.int32)


def hash_init(key: jax.Array, cfg: HashGridCfg) -> dict:
    keys = jax.random.split(key, cfg.num_levels)
    tables = [
        1e-2 * jax.random.normal(k, (cfg.table_size, cfg.channels), jnp.float32)
        for k in keys
    ]
    return {"tables": tables}


def hash_level_ids_weights(points: jnp.ndarray, cfg: HashGridCfg, level: int):
    res = cfg.level_res(level)
    g = _to_grid_coords(points, res)
    base = jnp.floor(g).astype(jnp.int32)
    frac = g - base
    corners = jnp.clip(base[:, None, :] + _CORNERS[None, :, :], 0, res - 1)
    if cfg.level_dense(level):
        ids = (corners[..., 0] * res + corners[..., 1]) * res + corners[..., 2]
        ids = ids % cfg.table_size
    else:
        ids = _hash_coords(corners, cfg.table_size)
    cw = jnp.where(_CORNERS[None, :, :] == 1, frac[:, None, :], 1.0 - frac[:, None, :])
    return ids, cw.prod(axis=-1)


def hash_query(params: dict, points: jnp.ndarray, cfg: HashGridCfg) -> jnp.ndarray:
    outs = []
    for level in range(cfg.num_levels):
        ids, w = hash_level_ids_weights(points, cfg, level)
        outs.append(gather_trilerp_ref(params["tables"][level], ids, w))
    return jnp.concatenate(outs, axis=-1)


# ----------------------------------------------------------------------------
# TensoRFGrid (VM decomposition)
# ----------------------------------------------------------------------------


@dataclass(frozen=True)
class TensoRFCfg:
    res: int = 64
    rank: int = 8
    channels: int = 8  # output channels


def tensorf_init(key: jax.Array, cfg: TensoRFCfg) -> dict:
    ks = jax.random.split(key, 7)
    planes = [
        0.1 * jax.random.normal(ks[i], (cfg.res, cfg.res, cfg.rank), jnp.float32)
        for i in range(3)
    ]
    lines = [
        0.1 * jax.random.normal(ks[3 + i], (cfg.res, cfg.rank), jnp.float32)
        for i in range(3)
    ]
    basis = jax.random.normal(ks[6], (3 * cfg.rank, cfg.channels), jnp.float32) / jnp.sqrt(
        3.0 * cfg.rank
    )
    return {"planes": planes, "lines": lines, "basis": basis}


def _bilerp(plane: jnp.ndarray, xy: jnp.ndarray, res: int) -> jnp.ndarray:
    g = _to_grid_coords(xy, res)
    b = jnp.floor(g).astype(jnp.int32)
    f = g - b
    b1 = jnp.minimum(b + 1, res - 1)
    v00 = plane[b[:, 0], b[:, 1]]
    v01 = plane[b[:, 0], b1[:, 1]]
    v10 = plane[b1[:, 0], b[:, 1]]
    v11 = plane[b1[:, 0], b1[:, 1]]
    w00 = (1 - f[:, :1]) * (1 - f[:, 1:2])
    w01 = (1 - f[:, :1]) * f[:, 1:2]
    w10 = f[:, :1] * (1 - f[:, 1:2])
    w11 = f[:, :1] * f[:, 1:2]
    return v00 * w00 + v01 * w01 + v10 * w10 + v11 * w11


def _lerp1d(line: jnp.ndarray, z: jnp.ndarray, res: int) -> jnp.ndarray:
    g = jnp.clip((z + 1.0) * 0.5 * (res - 1), 0.0, res - 1 - 1e-4)
    b = jnp.floor(g).astype(jnp.int32)
    f = (g - b)[:, None]
    return line[b] * (1 - f) + line[jnp.minimum(b + 1, res - 1)] * f


_VM_AXES = ((0, 1, 2), (0, 2, 1), (1, 2, 0))  # (plane axes, line axis)


def tensorf_query(params: dict, points: jnp.ndarray, cfg: TensoRFCfg) -> jnp.ndarray:
    feats = []
    for k, (a, b, c) in enumerate(_VM_AXES):
        plane_feat = _bilerp(params["planes"][k], points[:, (a, b)], cfg.res)
        line_feat = _lerp1d(params["lines"][k], points[:, c], cfg.res)
        feats.append(plane_feat * line_feat)  # [S, rank]
    return jnp.concatenate(feats, axis=-1) @ params["basis"]
