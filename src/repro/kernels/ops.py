"""Jit'd wrappers: pad/reorder host-visible shapes into kernel geometry.

These are the public entry points; each returns exactly what the matching
oracle in ``ref.py`` returns (tested with shape/dtype sweeps).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import streaming
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_nerf_mlp as _mlp
from repro.kernels import gather_trilerp as _gt
from repro.kernels import streaming_pipeline as _sp
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

    Builds the RIT, runs the Pallas GU kernel per MVoxel, scatters results
    back to sample order. RIT-overflow samples (capacity exceeded) take the
    reference (non-streaming) path — the paper's fallback. Output matches
    ``grids.gather_trilerp_ref`` on the original table.

    ``mv_table`` is the per-MVoxel halo re-layout of ``table``; pass the
    prebuilt one (``NerfModel.prepare_streaming`` caches it per params) so the
    table build is hoisted out of the per-frame hot path. When omitted it is
    built here (correct, but re-laid-out on every call).

    ``seg`` ([S] int32, with static ``num_seg``) is the flat ray-batch
    core's segment axis: samples from ``num_seg`` serving sessions share
    this ONE gather call, but the RIT is bucketed per ``(segment, MVoxel)``
    pair, so each session keeps exactly the per-MVoxel capacity (and
    overflow-fallback set) its exclusive single-session run would have.
    Samples with ``seg >= num_seg`` (chunk padding) are dropped from the
    table — they consume no capacity and their output is unspecified.

    ``scene_of_seg`` ([num_seg] int32, requires ``seg``) switches to the
    mixed-scene path: ``table`` is the stacked resident set ``[K, res^3,
    C]``, ``mv_table`` the stacked re-laid set ``[K, num_mv, P, C]``, and
    each segment gathers from its own scene's rows (bit-identical per
    segment to its exclusive single-scene run — the kernel body and the
    fallback einsum are unchanged).
    """
    scened = scene_of_seg is not None
    if scened and seg is None:
        raise ValueError("scene_of_seg requires the seg array (the segment"
                         "→scene map is indexed by segment id)")
    s = points.shape[0]
    if mv_table is None:
        if scened:
            raise ValueError("mixed-scene gather needs the prebuilt stacked "
                             "mv_table [K, num_mv, P, C]")
        mv_table = streaming.build_mvoxel_table(table, cfg)  # [M, P, C]
    with jax.named_scope("rit_build"):
        mv = streaming.mvoxel_ids(points, cfg)
        num_mv = cfg.num_mvoxels
        if seg is not None and (num_seg > 1 or scened):
            # combined (segment, mvoxel) bucket id, segment-major; padding
            # segments land out of range and drop out of the table build
            bucket = jnp.where(seg < num_seg, seg * num_mv + mv,
                               num_seg * num_mv)
            num_slots = num_seg * num_mv
        else:
            bucket, num_slots = mv, num_mv
        rit = streaming.build_rit(bucket, cfg, num_slots=num_slots)
        local_ids, w = streaming.local_corner_ids(points, cfg)
        # match the (possibly bank-interleaved) physical row order of mv_table
        local_ids = streaming.remap_local_ids(local_ids, cfg)
        # per-bucket sample blocks (RIT layout); padded columns use id 0 /
        # weight 0
        ids_mv, w_mv = _sp.rit_sample_blocks(local_ids, w, rit.samples)

    with jax.named_scope("gather"):
        if scened:
            seg_tables = mv_table[scene_of_seg]  # [num_seg, num_mv, P, C]
            out_mv = _gt.gather_trilerp_mvoxels_per_seg(
                seg_tables, ids_mv, w_mv, num_seg=num_seg,
                interpret=interpret)
        elif seg is not None and num_seg > 1:
            out_mv = _gt.gather_trilerp_mvoxels_segmented(
                mv_table, ids_mv, w_mv, num_seg=num_seg, interpret=interpret)
        else:
            out_mv = _gt.gather_trilerp_mvoxels(mv_table, ids_mv, w_mv,
                                                interpret=interpret)

    feats = _sp.scatter_rit_outputs(out_mv, rit.samples, s)

    # overflow fallback (pixel-centric path for the spilled samples)
    if scened:
        def gather(ids, weights):
            scn = scene_of_seg[jnp.clip(seg, 0, num_seg - 1)]
            return _sp.gather_trilerp_ref_scened(table, scn, ids, weights)
    else:
        def gather(ids, weights):
            return _sp.fallback_gather(table, ids, weights)
    return _sp.select_fallback(feats, rit.overflow, points, cfg, gather)


# ---------------------------------------------------------------------------
# fused NeRF MLP
# ---------------------------------------------------------------------------


def nerf_mlp(feats: jnp.ndarray, direnc: jnp.ndarray, params: dict, *,
             block: int = 256, interpret: bool | None = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused decoder. params = repro.nerf.mlp decoder params (mode='mlp').
    Returns (sigma [S], rgb [S,3])."""
    s = feats.shape[0]
    s_pad = round_up(max(s, 1), block)
    fp = jnp.pad(feats, ((0, s_pad - s), (0, 0)))
    dp = jnp.pad(direnc, ((0, s_pad - s), (0, 0)))
    out = _mlp.fused_nerf_mlp(
        fp, dp, params["w1"], params["b1"][None, :], params["w2"],
        params["b2"][None, :], params["w_sigma"], params["w_rgb"],
        params["b_rgb"][None, :], block=block, interpret=interpret)
    out = out[:s]
    return out[:, 0], out[:, 1:4]


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
