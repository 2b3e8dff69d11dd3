"""DVGO's dense voxel grid (``"arch": "dvgo_dense"``): one ``[res^3, C]``
feature table, trilinearly interpolated, decoded by the direct decoder
(the table holds ``(sigma, r, g, b, 0...)``) or by an MLP of one hidden
width with a 9-wide view-direction encoding.

The harness finds this module by the ``arch`` key of a configuration and
uses it for everything that depends on how the model stores and decodes
its field:

- :func:`make_weights`: the table, baked from the benchmark's scene on
  the device, and the decoder drawn from the seed;
- :func:`config_fields` and :func:`build_model`: the program's model over
  those weights (the only code here that imports the program);
- :func:`reference_key` and :func:`field`: the plain reference's field,
  in ``jax.numpy`` at the precision it is given;
- :func:`gather_work`, :func:`decoder_work` and :func:`table_sweep_bytes`:
  the work a tick requires of the feature gather and the decoder;
- ``TINY``: the keys a CPU test overrides to serve the configuration at a
  size a test run holds.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import reference
import weights as scene

TINY = dict(grid_res=16, res=16, num_samples=16, pool_bucket=256,
            ray_chunk=256)

F32 = 4
CORNERS = 8
DIR_ENC = 9  # view-direction encoding width of the MLP decoder


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _bake(centers, radii, albedos, res: int, channels: int):
    axes = jnp.linspace(-1.0, 1.0, res)
    x, y, z = jnp.meshgrid(axes, axes, axes, indexing="ij")
    p = jnp.stack([x, y, z], axis=-1).reshape(-1, 3)
    sigma, rgb = scene.scene_field(p, centers, radii, albedos)
    table = jnp.concatenate([sigma[:, None], rgb], axis=-1)
    return jnp.pad(table, ((0, 0), (0, channels - 4)))


def _decoder(key, channels: int, hidden: int, dir_dims: int = DIR_ENC):
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])

    return {
        "w1": normal(k1, (channels, hidden)),
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": normal(k2, (hidden, hidden)),
        "b2": jnp.zeros((hidden,), jnp.float32),
        "w_sigma": normal(k3, (hidden, 1)),
        "w_rgb": normal(k4, (hidden + dir_dims, 3)),
        "b_rgb": jnp.zeros((3,), jnp.float32),
    }


@partial(jax.jit, static_argnames=("res", "channels", "hidden", "mlp"))
def _make(centers, radii, albedos, key, *, res, channels, hidden, mlp):
    out = {"table": _bake(centers, radii, albedos, res, channels)}
    out["decoder"] = _decoder(key, channels, hidden) if mlp else {}
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """``{"table": [res^3, C], "decoder": {...} or {}}`` on the device, in
    float32 as served, made in one jitted call: the configuration's scene
    baked at the grid vertices into (sigma, r, g, b) and zero-padded to the
    channel count; for an MLP configuration, decoder weights drawn from
    the seed."""
    centers, radii, albedos = scene.scene_spheres(cfg["scene"])
    return _make(centers, radii, albedos, scene.seed_key(seed),
                 res=cfg["grid_res"], channels=cfg["channels"],
                 hidden=cfg["mlp_hidden"], mlp=cfg["decoder"] == "mlp")


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def config_fields(cfg: dict) -> dict:
    """The model-shape fields of the program's ``RenderConfig``."""
    return dict(grid_res=cfg["grid_res"], channels=cfg["channels"],
                num_samples=cfg["num_samples"], decoder=cfg["decoder"],
                stream_capacity=cfg["rit_capacity"])


def build_model(cfg: dict, rcfg, weights: dict):
    """``(model, params)``: the program's DVGO model on the streaming
    backend, and the benchmark's weights as its parameters."""
    from repro.nerf import models

    model, _ = models.make_model(
        "dvgo", grid_res=cfg["grid_res"], channels=cfg["channels"],
        decoder=cfg["decoder"], mlp_hidden=cfg["mlp_hidden"],
        num_samples=cfg["num_samples"], near=cfg["near"], far=cfg["far"],
        backend="streaming", stream_capacity=cfg["rit_capacity"],
        pallas_interpret=rcfg.pallas_interpret)
    return model, {"table": weights["table"], "decoder": weights["decoder"]}


# ---------------------------------------------------------------------------
# the plain reference's field
# ---------------------------------------------------------------------------


def reference_key(cfg: dict) -> tuple:
    """What :func:`field` reads of the configuration: ``(grid_res,)``."""
    return (cfg["grid_res"],)


def _trilinear(table, pts, grid_res: int, precision):
    g = jnp.clip((pts + 1.0) * 0.5 * (grid_res - 1), 0.0, grid_res - 1 - 1e-4)
    base = jnp.floor(g).astype(jnp.int32)
    frac = g - base
    corners = np.array([[i, j, k] for i in (0, 1) for j in (0, 1)
                        for k in (0, 1)], np.int32)
    c = jnp.clip(base[:, None, :] + corners[None], 0, grid_res - 1)
    ids = (c[..., 0] * grid_res + c[..., 1]) * grid_res + c[..., 2]
    w = jnp.where(corners[None] == 1, frac[:, None, :],
                  1.0 - frac[:, None, :]).prod(axis=-1)
    # one [S, C] gather per corner, summed in corner order (a stacked
    # [S, 8, C] gather pads the 8-corner axis to 128 lanes on a TPU)
    acc = reference.multiply(w[:, 0:1], table[ids[:, 0]], precision)
    for v in range(1, 8):
        acc = acc + reference.multiply(w[:, v:v + 1], table[ids[:, v]],
                                       precision)
    return acc


def _decode(dec, feats, dirs, precision):
    if not dec:
        return jnp.maximum(feats[:, 0], 0.0), jnp.clip(feats[:, 1:4], 0.0, 1.0)
    mm = partial(reference.contract, "sc,ch->sh", precision=precision)
    h = jax.nn.relu(mm(feats, dec["w1"]) + dec["b1"])
    h = jax.nn.relu(mm(h, dec["w2"]) + dec["b2"])
    sigma = jax.nn.softplus(mm(h, dec["w_sigma"]))[:, 0]
    x, y, z = dirs[:, 0:1], dirs[:, 1:2], dirs[:, 2:3]
    enc = jnp.concatenate([dirs, x * y, y * z, x * z, x * x, y * y, z * z], -1)
    rgb = jax.nn.sigmoid(mm(jnp.concatenate([h, enc], -1), dec["w_rgb"])
                         + dec["b_rgb"])
    return sigma, rgb


def field(weights, pts, dirs, key: tuple, precision: str):
    """``(sigma [S], rgb [S, 3])`` at the points ``pts [S, 3]`` seen along
    the unit directions ``dirs [S, 3]``. ``key`` is
    :func:`reference_key`'s."""
    (grid_res,) = key
    feats = _trilinear(weights["table"], pts, grid_res, precision)
    return _decode(weights["decoder"], feats, dirs, precision)


# ---------------------------------------------------------------------------
# required work
# ---------------------------------------------------------------------------


def table_sweep_bytes(cfg: dict) -> int:
    """One sweep of the MVoxel halo table: every (edge+1)^3 block once."""
    per_edge = -(-cfg["grid_res"] // cfg["mvoxel_edge"])
    halo = (cfg["mvoxel_edge"] + 1) ** 3
    return per_edge ** 3 * halo * cfg["channels"] * F32


def gather_work(cfg: dict, samples: int) -> dict:
    """Trilinear gather of ``samples`` samples from the MVoxel table: a
    weighted sum of 8 corner rows per sample; corner ids and weights in,
    features out, and one sweep of the table."""
    c = cfg["channels"]
    return {"flops": samples * CORNERS * c * 2,
            "bytes": (table_sweep_bytes(cfg)
                      + samples * (CORNERS * F32 * 2 + c * F32))}


def _decoder_flops_per_sample(cfg: dict) -> int:
    if cfg["decoder"] != "mlp":
        return 4  # sigma clamp and three colour clips
    c, h = cfg["channels"], cfg["mlp_hidden"]
    return 2 * (c * h + h * h + h + (h + DIR_ENC) * 3)


def decoder_work(cfg: dict, samples: int) -> dict:
    """The decoder over ``samples`` samples: features and the direction
    encoding in, (sigma, rgb) out, the MLP's weights once."""
    c, h = cfg["channels"], cfg["mlp_hidden"]
    weights = (c * h + h + h * h + h + h + (h + DIR_ENC) * 3 + 3) * F32
    return {"flops": samples * _decoder_flops_per_sample(cfg),
            "bytes": samples * (c + DIR_ENC + 4) * F32 + weights}
