"""Feature Computation (``F``): decode gathered features into (sigma, rgb).

Three decoders:
* ``mlp``    — the repository's lightweight radiance MLP (a density head on
               the hidden layer, a 9-wide quadratic direction encoding).
* ``rgbnet`` — DVGO's fine stage (Sun et al., CVPR 2022, arXiv 2111.11215):
               channel 0 of the features is the raw density, post-activated
               as ``softplus(density + shift)`` in voxel units; channels
               1.. are the k0 features, decoded with the view direction's
               positional encoding by ``rgbnet`` (``rgbnet_direct``: one
               MLP of two hidden layers, sigmoid colour).
* ``direct`` — features already hold (sigma_raw, r, g, b); used by grids baked
               from analytic scenes so quality experiments are deterministic.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class DecoderCfg:
    mode: str = "mlp"  # mlp | rgbnet | direct
    in_channels: int = 8
    hidden: int = 64
    view_dirs: bool = True
    # rgbnet only: frequencies of the view-direction encoding, the alpha an
    # untrained density gives one voxel step, and the voxel edge in world
    # units (density is activated per voxel of travel)
    viewbase_pe: int = 4
    alpha_init: float = 1e-2
    voxel_size: float = 1.0

    @property
    def density_shift(self) -> float:
        """DVGO's ``act_shift``: ``softplus(0 + shift)`` per voxel step
        gives ``alpha_init``."""
        return float(np.log(1.0 / (1.0 - self.alpha_init) - 1.0))

    @property
    def pe_dims(self) -> int:
        """Width of the view encoding ``[d, sin(d 2^k), cos(d 2^k)]``."""
        return 3 + 3 * 2 * self.viewbase_pe

    @property
    def rgbnet_in(self) -> int:
        """rgbnet's input width: the k0 channels and the view encoding."""
        return self.in_channels - 1 + self.pe_dims


def _dir_enc(dirs: jnp.ndarray) -> jnp.ndarray:
    """Cheap view-direction encoding: raw + 2nd order terms (9 dims)."""
    x, y, z = dirs[:, 0:1], dirs[:, 1:2], dirs[:, 2:3]
    return jnp.concatenate([dirs, x * y, y * z, x * z, x * x, y * y, z * z], axis=-1)


def view_pe(dirs: jnp.ndarray, viewbase_pe: int) -> jnp.ndarray:
    """DVGO's view encoding ``[d, sin(d 2^k), cos(d 2^k)]``, k = 0 ..
    ``viewbase_pe - 1``; within the sines (and the cosines) the frequency
    varies fastest, axis by axis. ``dirs`` [S, 3] -> [S, 3 + 6 pe]."""
    freq = 2.0 ** jnp.arange(viewbase_pe, dtype=dirs.dtype)
    emb = (dirs[:, :, None] * freq).reshape(dirs.shape[0], -1)
    return jnp.concatenate([dirs, jnp.sin(emb), jnp.cos(emb)], axis=-1)


def density_activation(density: jnp.ndarray, cfg: DecoderCfg) -> jnp.ndarray:
    """Post-activated density in world units: ``softplus(density + shift)``
    is DVGO's density per voxel of travel, so ``sigma * delta`` over a
    world-space step ``delta`` is its ``softplus * interval``."""
    return jax.nn.softplus(density + cfg.density_shift) / cfg.voxel_size


def decoder_init(key: jax.Array, cfg: DecoderCfg) -> dict:
    if cfg.mode == "direct":
        return {}
    if cfg.mode == "rgbnet":
        dims = (cfg.rgbnet_in, cfg.hidden, cfg.hidden, 3)
        out = {}
        for i, (k, d_in, d_out) in enumerate(zip(jax.random.split(key, 3),
                                                 dims[:-1], dims[1:]),
                                             start=1):
            out[f"w{i}"] = (jax.random.normal(k, (d_in, d_out))
                            / jnp.sqrt(d_in))
            out[f"b{i}"] = jnp.zeros((d_out,))
        return out
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d_in = cfg.in_channels
    d_dir = 9 if cfg.view_dirs else 0
    s = lambda *shape: 1.0 / jnp.sqrt(shape[0])
    return {
        "w1": jax.random.normal(k1, (d_in, cfg.hidden)) * s(d_in),
        "b1": jnp.zeros((cfg.hidden,)),
        "w2": jax.random.normal(k2, (cfg.hidden, cfg.hidden)) * s(cfg.hidden),
        "b2": jnp.zeros((cfg.hidden,)),
        "w_sigma": jax.random.normal(k3, (cfg.hidden, 1)) * s(cfg.hidden),
        "w_rgb": jax.random.normal(k4, (cfg.hidden + d_dir, 3)) * s(cfg.hidden + d_dir),
        "b_rgb": jnp.zeros((3,)),
    }


def decode(params: dict, feats: jnp.ndarray, dirs: jnp.ndarray, cfg: DecoderCfg
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """feats [S, C], dirs [S, 3] -> (sigma [S], rgb [S,3])."""
    if cfg.mode == "direct":
        sigma = jnp.maximum(feats[:, 0], 0.0)
        rgb = jnp.clip(feats[:, 1:4], 0.0, 1.0)
        return sigma, rgb
    if cfg.mode == "rgbnet":
        hi = jax.lax.Precision.HIGHEST
        x = jnp.concatenate([feats[:, 1:], view_pe(dirs, cfg.viewbase_pe)], -1)
        h = jax.nn.relu(jnp.dot(x, params["w1"], precision=hi) + params["b1"])
        h = jax.nn.relu(jnp.dot(h, params["w2"], precision=hi) + params["b2"])
        rgb = jax.nn.sigmoid(jnp.dot(h, params["w3"], precision=hi)
                             + params["b3"])
        return density_activation(feats[:, 0], cfg), rgb
    h = jax.nn.relu(feats @ params["w1"] + params["b1"])
    h = jax.nn.relu(h @ params["w2"] + params["b2"])
    sigma = jax.nn.softplus(h @ params["w_sigma"]).squeeze(-1)
    rgb_in = jnp.concatenate([h, _dir_enc(dirs)], axis=-1) if cfg.view_dirs else h
    rgb = jax.nn.sigmoid(rgb_in @ params["w_rgb"] + params["b_rgb"])
    return sigma, rgb


def decoder_flops(cfg: DecoderCfg) -> int:
    """MACs*2 per ray sample — used by the cost model (NPU workload)."""
    if cfg.mode == "direct":
        return 8
    if cfg.mode == "rgbnet":
        h = cfg.hidden
        return 2 * (cfg.rgbnet_in * h + h * h + h * 3)
    d_dir = 9 if cfg.view_dirs else 0
    macs = cfg.in_channels * cfg.hidden + cfg.hidden * cfg.hidden
    macs += cfg.hidden + (cfg.hidden + d_dir) * 3
    return 2 * macs
