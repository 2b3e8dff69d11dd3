"""Jit'd wrappers: pad/reorder host-visible shapes into kernel geometry.

These are the public entry points; each returns exactly what the matching
oracle in ``ref.py`` returns (tested with shape/dtype sweeps).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import streaming
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_nerf_mlp as _mlp
from repro.kernels import gather_trilerp as _gt
from repro.kernels import streaming_pipeline as _sp
from repro.nerf import mlp
from repro.utils import round_up


# ---------------------------------------------------------------------------
# gather_trilerp: full streaming pipeline around the GU kernel
# ---------------------------------------------------------------------------


def gather_features_streaming(table: jnp.ndarray, points: jnp.ndarray,
                              cfg: streaming.StreamingCfg, *,
                              mv_table: jnp.ndarray | None = None,
                              seg: jnp.ndarray | None = None,
                              num_seg: int = 1,
                              scene_of_seg: jnp.ndarray | None = None,
                              interpret: bool | None = None) -> jnp.ndarray:
    """Memory-centric feature gather of ``points`` from a dense vertex table.

    Builds the ragged RIT, runs the Pallas GU kernel over its blocks in
    MVoxel order, and takes each sample's row back to sample order
    (:func:`repro.kernels.streaming_pipeline.stream_gather`). Every sample
    streams, however many pile into one MVoxel. Output matches
    ``grids.gather_trilerp_ref`` on the original table.

    ``mv_table`` is the per-MVoxel halo re-layout of ``table``; pass the
    prebuilt one (``NerfModel.prepare_streaming`` caches it per params) so the
    table build is hoisted out of the per-frame hot path. When omitted it is
    built here (correct, but re-laid-out on every call).

    ``seg`` ([S] int32, with static ``num_seg``) is the flat ray-batch
    core's segment axis: samples from ``num_seg`` serving sessions share
    this ONE gather call. Each output depends only on its own sample, so a
    session's features are bit-identical to its exclusive run. Samples
    with ``seg >= num_seg`` (chunk padding) are dropped from the table —
    they take no column and read zero.

    ``scene_of_seg`` ([num_seg] int32, requires ``seg``) switches to the
    mixed-scene path: ``mv_table`` is the stacked re-laid set ``[K,
    num_mv, P, C]``, and each segment gathers from its own scene's page
    (``table`` is then unused).
    """
    if scene_of_seg is not None:
        if seg is None:
            raise ValueError("scene_of_seg requires the seg array (the "
                             "segment→scene map is indexed by segment id)")
        if mv_table is None:
            raise ValueError("mixed-scene gather needs the prebuilt stacked "
                             "mv_table [K, num_mv, P, C]")
    if mv_table is None:
        mv_table = streaming.build_mvoxel_table(table, cfg)  # [M, P, C]
    out, rit = _sp.stream_gather(_gt.gather_trilerp_mvoxels_segmented,
                                 mv_table, points, cfg, seg, num_seg,
                                 scene_of_seg, interpret=interpret)
    return _sp.unpermute_rit_outputs(out, rit.col)


# ---------------------------------------------------------------------------
# fused NeRF MLP
# ---------------------------------------------------------------------------


def nerf_mlp(feats: jnp.ndarray, direnc: jnp.ndarray, params: dict, *,
             block: int = 256, interpret: bool | None = None,
             tick: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused decoder. params = repro.nerf.mlp decoder params (mode='mlp').
    Returns (sigma [S], rgb [S,3]). ``tick`` runs it as the fused tick's
    kernel (:func:`repro.kernels.fused_nerf_mlp.fused_nerf_mlp`), otherwise
    as the staged renders' (``staged_nerf_mlp``)."""
    s = feats.shape[0]
    s_pad = round_up(max(s, 1), block)
    fp = jnp.pad(feats, ((0, s_pad - s), (0, 0)))
    dp = jnp.pad(direnc, ((0, s_pad - s), (0, 0)))
    kernel = _mlp.fused_nerf_mlp if tick else _mlp.staged_nerf_mlp
    out = kernel(
        fp, dp, params["w1"], params["b1"][None, :], params["w2"],
        params["b2"][None, :], params["w_sigma"], params["w_rgb"],
        params["b_rgb"][None, :], block=block, interpret=interpret)
    out = out[:s]
    return out[:, 0], out[:, 1:4]


def dvgo_rgbnet(feats: jnp.ndarray, dirs: jnp.ndarray, params: dict,
                cfg: "mlp.DecoderCfg", *, block: int = 1024,
                interpret: bool | None = None, tick: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """DVGO's fine-stage decoder (``repro.nerf.mlp`` mode ``rgbnet``)
    through the fused kernel. ``feats`` [S, C] hold the raw density
    (channel 0) and the k0 features, ``dirs`` [S, 3] the unit view
    directions. Returns (sigma [S] in world units, rgb [S, 3]). The kernel
    reads both channel-major, samples on lanes; rgbnet's first layer is
    split by its input rows (k0, d, sines, cosines). ``tick`` as in
    :func:`nerf_mlp`."""
    s, c = feats.shape
    k, pe = c - 1, cfg.viewbase_pe
    s_pad = round_up(max(s, 1), block)
    x = jnp.pad(feats, ((0, s_pad - s), (0, 0))).T
    d = jnp.pad(dirs, ((0, s_pad - s), (0, 0))).T
    w1 = params["w1"]
    # row a*pe + k of the frequency matrix picks axis a at 2^k
    freq = np.kron(np.eye(3), 2.0 ** np.arange(pe)[:, None])
    weights = (
        jnp.pad(w1[:k].T, ((0, 0), (1, 0))),  # the density row feeds none
        w1[k:k + 3].T, w1[k + 3:k + 3 + 3 * pe].T, w1[k + 3 + 3 * pe:].T,
        jnp.asarray(freq, feats.dtype), params["b1"][:, None],
        params["w2"].T, params["b2"][:, None],
        params["w3"].T, params["b3"][:, None])
    kernel = _mlp.fused_nerf_mlp if tick else _mlp.staged_nerf_mlp
    out = kernel(x, d, *weights, block=block, interpret=interpret,
                 density=(cfg.density_shift, cfg.voxel_size))
    return out[0, :s], out[1:4, :s].T


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def mha(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *, causal: bool = True,
        block_q: int = 128, block_k: int = 128, interpret: bool | None = None
        ) -> jnp.ndarray:
    """Flash attention with seq padding. q [B,H,Sq,D], k/v [B,KVH,Sk,D]."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(sk, 8))
    sqp, skp = round_up(sq, bq), round_up(sk, bk)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    sm_scale = d**-0.5
    # padded KV rows are masked explicitly inside the kernel (kv_len)
    out = _fa.flash_attention(qp, kp, vp, causal=causal, sm_scale=sm_scale,
                              block_q=bq, block_k=bk,
                              kv_len=sk if skp > sk else None,
                              interpret=interpret)
    return out[:, :, :sq]
