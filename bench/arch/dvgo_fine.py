"""DVGO's fine stage (``"arch": "dvgo_fine"``; Sun, Sun and Chen, CVPR
2022, arXiv 2111.11215, ``configs/default.py`` ``fine_model_and_render``):
one ``[res^3, 1 + K]`` table whose channel 0 is the raw density grid and
whose channels 1..K are the k0 feature grid, both trilinearly
interpolated. For a point ``x`` seen along the unit direction ``d``:

- ``sigma(x) = softplus(density(x) + shift) / voxel_size``, with ``shift =
  ln(1 / (1 - alpha_init) - 1)`` and ``voxel_size = 2 / (res - 1)``: DVGO's
  post-activated density per voxel of travel, so that ``alpha = 1 -
  exp(-sigma * delta)`` over a world-space step ``delta`` is DVGO's ``1 -
  exp(-softplus(density + shift) * interval)``;
- ``pe(d) = [d, sin(d 2^k), cos(d 2^k)]``, k = 0 .. ``viewbase_pe - 1``,
  the frequency varying fastest within each axis;
- ``rgb = sigmoid(W3 relu(W2 relu(W1 [k0(x), pe(d)] + b1) + b2) + b3)``
  (``rgbnet_direct``, widths 39 -> 128 -> 128 -> 3).

The harness finds this module by the ``arch`` key of a configuration; its
functions are those of ``dvgo_dense.py``:

- :func:`make_weights`: the table and rgbnet, on the device from the seed;
- :func:`config_fields` and :func:`build_model`: the program's model over
  those weights (the only code here that imports the program);
- :func:`reference_key` and :func:`field`: the plain reference's field,
  the equations above in ``jax.numpy`` at the precision it is given;
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
# the post-activated density a vertex of empty space gets: softplus of
# about -20.7, so that no vertex's raw density is infinite
EMPTY = 1e-9


def voxel_size(grid_res: int) -> float:
    """The grid's vertex spacing over the [-1, 1] cube."""
    return 2.0 / (grid_res - 1)


def density_shift(alpha_init: float) -> float:
    """DVGO's ``act_shift``: a raw density of 0 gives ``alpha_init`` over
    one voxel."""
    return float(np.log(1.0 / (1.0 - alpha_init) - 1.0))


def pe_dims(cfg: dict) -> int:
    return 3 + 3 * 2 * cfg["viewbase_pe"]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _bake_density(centers, radii, albedos, res: int, shift: float):
    """Raw density at the grid vertices whose post-activation is the
    scene's density there: ``softplus(raw + shift) / voxel_size =
    sigma``."""
    axes = jnp.linspace(-1.0, 1.0, res)
    x, y, z = jnp.meshgrid(axes, axes, axes, indexing="ij")
    p = jnp.stack([x, y, z], axis=-1).reshape(-1, 3)
    sigma, _ = scene.scene_field(p, centers, radii, albedos)
    act = jnp.maximum(sigma * voxel_size(res), EMPTY)
    # softplus^-1(a) = log(expm1(a))
    return jnp.log(jnp.expm1(act)) - shift


def _rgbnet(key, widths):
    keys = jax.random.split(key, len(widths) - 1)
    out = {}
    for i, (k, d_in, d_out) in enumerate(zip(keys, widths[:-1], widths[1:]),
                                         start=1):
        out[f"w{i}"] = (jax.random.normal(k, (d_in, d_out), jnp.float32)
                        / np.sqrt(d_in))
        out[f"b{i}"] = jnp.zeros((d_out,), jnp.float32)
    return out


@partial(jax.jit, static_argnames=("res", "k0", "widths", "shift"))
def _make(centers, radii, albedos, key, *, res, k0, widths, shift):
    k_grid, k_net = jax.random.split(key)
    density = _bake_density(centers, radii, albedos, res, shift)
    feats = jax.random.normal(k_grid, (res ** 3, k0), jnp.float32)
    return {"table": jnp.concatenate([density[:, None], feats], axis=-1),
            "decoder": _rgbnet(k_net, widths)}


def make_weights(cfg: dict, seed: int) -> dict:
    """``{"table": [res^3, 1 + K], "decoder": {w1, b1, w2, b2, w3, b3}}`` on
    the device, in float32 as served, made in one jitted call: the raw
    density baked from the configuration's scene so that its
    post-activation is the scene's density at every vertex, the k0
    features drawn N(0, 1), and rgbnet's weights N(0, 1/fan-in) with zero
    biases, each from the seed."""
    centers, radii, albedos = scene.scene_spheres(cfg["scene"])
    widths = tuple(cfg["rgbnet"])
    if widths[0] != cfg["channels"] - 1 + pe_dims(cfg):
        raise ValueError(f"rgbnet's input width {widths[0]} is not the k0 "
                         f"channels and the view encoding")
    return _make(centers, radii, albedos, scene.seed_key(seed),
                 res=cfg["grid_res"], k0=cfg["channels"] - 1, widths=widths,
                 shift=density_shift(cfg["alpha_init"]))


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def config_fields(cfg: dict) -> dict:
    """The model-shape fields of the program's ``RenderConfig``."""
    return dict(grid_res=cfg["grid_res"], channels=cfg["channels"],
                num_samples=cfg["num_samples"], decoder="rgbnet",
                stream_capacity=cfg["rit_capacity"])


def build_model(cfg: dict, rcfg, weights: dict):
    """``(model, params)``: the program's DVGO model with its fine-stage
    decoder on the streaming backend, and the benchmark's weights as its
    parameters. A program without that decoder refuses the keys here."""
    from repro.nerf import models

    hidden = cfg["rgbnet"][1:-1]
    if len(set(hidden)) != 1 or len(hidden) != 2:
        raise ValueError(f"rgbnet {cfg['rgbnet']}: the program runs two "
                         f"hidden layers of one width")
    model, _ = models.make_model(
        "dvgo", grid_res=cfg["grid_res"], channels=cfg["channels"],
        decoder="rgbnet", mlp_hidden=hidden[0],
        viewbase_pe=cfg["viewbase_pe"], alpha_init=cfg["alpha_init"],
        num_samples=cfg["num_samples"], near=cfg["near"], far=cfg["far"],
        backend="streaming", stream_capacity=cfg["rit_capacity"],
        pallas_interpret=rcfg.pallas_interpret)
    return model, {"table": weights["table"], "decoder": weights["decoder"]}


# ---------------------------------------------------------------------------
# the plain reference's field
# ---------------------------------------------------------------------------


def reference_key(cfg: dict) -> tuple:
    """What :func:`field` reads of the configuration: ``(grid_res,
    viewbase_pe, alpha_init)``."""
    return (cfg["grid_res"], cfg["viewbase_pe"], float(cfg["alpha_init"]))


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
    # one [S, C] gather per corner, summed in corner order
    acc = reference.multiply(w[:, 0:1], table[ids[:, 0]], precision)
    for v in range(1, 8):
        acc = acc + reference.multiply(w[:, v:v + 1], table[ids[:, v]],
                                       precision)
    return acc


def _view_encoding(dirs, viewbase_pe: int):
    freq = 2.0 ** np.arange(viewbase_pe, dtype=np.float32)
    # [S, 3, pe] -> [S, 3 pe]: axis by axis, the frequency fastest
    emb = (dirs[:, :, None] * freq).reshape(dirs.shape[0], 3 * viewbase_pe)
    return jnp.concatenate([dirs, jnp.sin(emb), jnp.cos(emb)], axis=-1)


def field(weights, pts, dirs, key: tuple, precision: str):
    """``(sigma [S], rgb [S, 3])`` at the points ``pts [S, 3]`` seen along
    the unit directions ``dirs [S, 3]``. ``key`` is
    :func:`reference_key`'s."""
    grid_res, viewbase_pe, alpha_init = key
    feats = _trilinear(weights["table"], pts, grid_res, precision)
    sigma = (jax.nn.softplus(feats[:, 0] + density_shift(alpha_init))
             / voxel_size(grid_res))
    net = weights["decoder"]
    mm = partial(reference.contract, "sc,ch->sh", precision=precision)
    x = jnp.concatenate([feats[:, 1:], _view_encoding(dirs, viewbase_pe)],
                        axis=-1)
    h = jax.nn.relu(mm(x, net["w1"]) + net["b1"])
    h = jax.nn.relu(mm(h, net["w2"]) + net["b2"])
    rgb = jax.nn.sigmoid(mm(h, net["w3"]) + net["b3"])
    return sigma, rgb


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
    weighted sum of 8 corner rows of every channel, density and k0 alike;
    corner ids and weights in, features out, and one sweep of the
    table."""
    c = cfg["channels"]
    return {"flops": samples * CORNERS * c * 2,
            "bytes": (table_sweep_bytes(cfg)
                      + samples * (CORNERS * F32 * 2 + c * F32))}


def _matmul_flops(widths) -> int:
    return sum(2 * a * b for a, b in zip(widths[:-1], widths[1:]))


def decoder_work(cfg: dict, samples: int) -> dict:
    """The decoder over ``samples`` samples: rgbnet's three matmuls
    (2 * (39*128 + 128*128 + 128*3) = 43,520 operations a sample at the
    published widths), the view encoding (one product, a sine and a cosine
    per frequency and axis) and the density's shift, softplus and scale;
    per sample the features and the direction in, (sigma, rgb) out; the
    weights once."""
    widths = cfg["rgbnet"]
    pe = 3 * cfg["viewbase_pe"]
    per_sample = _matmul_flops(widths) + 3 * pe + 3
    weights = sum(a * b + b for a, b in zip(widths[:-1], widths[1:])) * F32
    return {"flops": samples * per_sample,
            "bytes": samples * (cfg["channels"] + 3 + 4) * F32 + weights}
