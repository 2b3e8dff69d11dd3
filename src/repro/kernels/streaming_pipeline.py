"""The unified streaming render pipeline's Pallas stage (ROADMAP item 4).

The staged tick runs reference render and pooled hole-fill as separate
programs, and each ``lax.map`` ray chunk inside them re-streams the ENTIRE
MVoxel halo table HBM→VMEM (one ``pallas_call`` sweep per chunk). Potamoi's
point — and this module's job — is to collapse that into ONE sweep per
tick: the tick's pooled hole samples and the NEXT tick's reference samples
are bucketed into two RITs over the same (segment, MVoxel) iteration
order, and a single fused kernel gathers BOTH sample sets from each halo
block while it is resident. Each (segment, MVoxel) feature block is
therefore fetched once per tick instead of once per ray-chunk per stage.

Grid layout mirrors ``gather_trilerp_mvoxels_segmented``: ``(num_mv,
num_seg)`` with segments innermost, so the Pallas grid pipeline stages one
halo block (double-buffered — the paper's §IV-A revolving buffer: block
``m+1`` DMAs in while ``m`` is being reduced) and reuses it across every
segment AND both pipeline stages before advancing.

Layout: the halo block arrives pre-laid-out by
``streaming.build_mvoxel_table`` (``StreamingCfg.layout``) and the local
corner ids pre-remapped — the kernel is layout-oblivious (the one-hot
select matmul works on any row order), which is what makes the
bank-interleaved layout bit-identical to the identity control.

``tick_traffic`` is the analytic bytes-moved accounting for this pipeline
(the Pallas path has no HLO to derive bytes from — the XLA/staged path's
numbers come from ``roofline.hlo_cost``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import streaming
from repro.kernels import gather_trilerp as _gt
from repro.kernels.common import resolve_interpret
from repro.nerf import grids


def _fused_kernel(tbl_ref, ih_ref, wh_ref, ir_ref, wr_ref, oh_ref, or_ref):
    """Both tick stages from ONE resident halo block: the pooled hole-fill
    samples (this tick) and the reference samples (next tick) gather while
    the block is in VMEM — the fetch-once-per-tick schedule."""
    tbl = tbl_ref[0]  # [P, C] — staged once, used twice
    oh_ref[0, 0] = _gt.gather_block(tbl, ih_ref[0, 0], wh_ref[0, 0],
                                    oh_ref.dtype)
    or_ref[0, 0] = _gt.gather_block(tbl, ir_ref[0, 0], wr_ref[0, 0],
                                    or_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_seg", "interpret"))
def fused_gather_dual(mv_table: jnp.ndarray,
                      ids_h: jnp.ndarray, w_h: jnp.ndarray,
                      ids_r: jnp.ndarray, w_r: jnp.ndarray, *,
                      num_seg: int, interpret: bool | None = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One MVoxel-table sweep serving BOTH tick stages.

    ``ids_h``/``w_h`` are the hole-fill RIT blocks
    ``[num_seg * num_mv, 8, cap_h]`` and ``ids_r``/``w_r`` the
    next-reference RIT blocks ``[num_seg * num_mv, 8, cap_r]`` (segment-
    major, same order and sample-on-lanes layout as
    :func:`gather_trilerp_mvoxels_segmented`). Returns ``([num_seg *
    num_mv, C, cap_h], [num_seg * num_mv, C, cap_r])``. The halo block's BlockSpec depends only on the outer (MVoxel)
    grid index, so the pipeline fetches it once per MVoxel and both
    stages' gathers run against the resident copy.
    """
    interpret = resolve_interpret(interpret)
    num_mv, p, c = mv_table.shape
    cap_h, cap_r = ids_h.shape[2], ids_r.shape[2]
    ih4 = ids_h.reshape(num_seg, num_mv, 8, cap_h)
    wh4 = w_h.reshape(num_seg, num_mv, 8, cap_h)
    ir4 = ids_r.reshape(num_seg, num_mv, 8, cap_r)
    wr4 = w_r.reshape(num_seg, num_mv, 8, cap_r)
    out_h, out_r = pl.pallas_call(
        _fused_kernel,
        grid=(num_mv, num_seg),  # seg innermost: halo block stays resident
        in_specs=[
            pl.BlockSpec((1, p, c), lambda m, s: (m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap_h), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap_h), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap_r), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap_r), lambda m, s: (s, m, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, cap_h), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, c, cap_r), lambda m, s: (s, m, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_seg, num_mv, c, cap_h),
                                 mv_table.dtype),
            jax.ShapeDtypeStruct((num_seg, num_mv, c, cap_r),
                                 mv_table.dtype),
        ],
        compiler_params=_gt.COMPILER_PARAMS,
        interpret=interpret,
    )(mv_table, ih4, wh4, ir4, wr4)
    return (out_h.reshape(num_seg * num_mv, c, cap_h),
            out_r.reshape(num_seg * num_mv, c, cap_r))


def _fused_kernel_per_seg(tbl_ref, ih_ref, wh_ref, ir_ref, wr_ref,
                          oh_ref, or_ref):
    """Mixed-scene fused stage: identical math to ``_fused_kernel``, but
    the staged halo block is the current *segment's scene's* block."""
    tbl = tbl_ref[0, 0]  # [P, C] — this segment's scene, staged once
    oh_ref[0, 0] = _gt.gather_block(tbl, ih_ref[0, 0], wh_ref[0, 0],
                                    oh_ref.dtype)
    or_ref[0, 0] = _gt.gather_block(tbl, ir_ref[0, 0], wr_ref[0, 0],
                                    or_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_seg", "interpret"))
def fused_gather_dual_per_seg(mv_tables: jnp.ndarray,
                              ids_h: jnp.ndarray, w_h: jnp.ndarray,
                              ids_r: jnp.ndarray, w_r: jnp.ndarray, *,
                              num_seg: int, interpret: bool | None = None
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mixed-scene variant of :func:`fused_gather_dual`: segment ``s``
    gathers from its own scene's halo table ``mv_tables[s]``
    (``[num_seg, num_mv, P, C]``, scene-selected by the caller from the
    stacked resident set). Grid, RIT blocks, and the inner
    ``gather_block`` math are unchanged, so a segment's outputs are
    bit-identical to its exclusive single-scene run; segments sharing a
    scene stage identical blocks, and with scene-adjacent slot ordering
    the tick still fetches each *distinct* resident block once."""
    interpret = resolve_interpret(interpret)
    _, num_mv, p, c = mv_tables.shape
    cap_h, cap_r = ids_h.shape[2], ids_r.shape[2]
    ih4 = ids_h.reshape(num_seg, num_mv, 8, cap_h)
    wh4 = w_h.reshape(num_seg, num_mv, 8, cap_h)
    ir4 = ids_r.reshape(num_seg, num_mv, 8, cap_r)
    wr4 = w_r.reshape(num_seg, num_mv, 8, cap_r)
    out_h, out_r = pl.pallas_call(
        _fused_kernel_per_seg,
        grid=(num_mv, num_seg),  # seg innermost: scene-adjacent block reuse
        in_specs=[
            pl.BlockSpec((1, 1, p, c), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap_h), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap_h), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap_r), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap_r), lambda m, s: (s, m, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, cap_h), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, c, cap_r), lambda m, s: (s, m, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_seg, num_mv, c, cap_h),
                                 mv_tables.dtype),
            jax.ShapeDtypeStruct((num_seg, num_mv, c, cap_r),
                                 mv_tables.dtype),
        ],
        compiler_params=_gt.COMPILER_PARAMS,
        interpret=interpret,
    )(mv_tables, ih4, wh4, ir4, wr4)
    return (out_h.reshape(num_seg * num_mv, c, cap_h),
            out_r.reshape(num_seg * num_mv, c, cap_r))


class _RitBlocks(NamedTuple):
    ids_mv: jnp.ndarray   # [num_slots, 8, cap] — layout-remapped local ids
    w_mv: jnp.ndarray     # [num_slots, 8, cap]
    samples: jnp.ndarray  # [num_slots, cap] sample ids (-1 pad)
    overflow: jnp.ndarray  # [T] bool


def rit_sample_blocks(local_ids: jnp.ndarray, w: jnp.ndarray,
                      samples: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-sample corner ids/weights ``[T, 8]`` → RIT-order kernel blocks
    ``[num_slots, 8, cap]`` (sample axis on lanes; pad columns: id 0,
    weight 0). ``samples`` is the RIT's ``[num_slots, cap]`` sample ids."""
    sample_slot = jnp.maximum(samples, 0)
    valid = (samples >= 0)[:, None, :]
    ids_mv = jnp.where(valid, jnp.swapaxes(local_ids[sample_slot], 1, 2), 0)
    w_mv = jnp.where(valid, jnp.swapaxes(w[sample_slot], 1, 2), 0.0)
    return ids_mv, w_mv


@jax.named_scope("rit_scatter")
def scatter_rit_outputs(out_mv: jnp.ndarray, samples: jnp.ndarray,
                        t: int) -> jnp.ndarray:
    """RIT-order kernel output ``[num_slots, C, cap]`` back to sample
    order ``[t, C]``; samples the RIT did not hold stay zero."""
    c = out_mv.shape[1]
    flat_sample = jnp.where(samples >= 0, samples, t).reshape(-1)
    rows = jnp.swapaxes(out_mv, 1, 2).reshape(-1, c)
    return jnp.zeros((t + 1, c), out_mv.dtype).at[flat_sample].set(rows)[:t]


@jax.named_scope("rit_build")
def _rit_blocks(points: jnp.ndarray, seg: jnp.ndarray, num_seg: int,
                cfg: streaming.StreamingCfg) -> _RitBlocks:
    """Bucket one sample set per (segment, MVoxel) and lay its corner
    ids/weights out in RIT order for the fused kernel (``cfg.capacity``
    samples per bucket; padding seg ids >= num_seg drop out)."""
    num_mv = cfg.num_mvoxels
    mv = streaming.mvoxel_ids(points, cfg)
    bucket = jnp.where(seg < num_seg, seg * num_mv + mv, num_seg * num_mv)
    rit = streaming.build_rit(bucket, cfg, num_slots=num_seg * num_mv)
    local_ids, w = streaming.local_corner_ids(points, cfg)
    local_ids = streaming.remap_local_ids(local_ids, cfg)
    ids_mv, w_mv = rit_sample_blocks(local_ids, w, rit.samples)
    return _RitBlocks(ids_mv, w_mv, rit.samples, rit.overflow)


def _scatter_with_fallback(out_mv: jnp.ndarray, blocks: _RitBlocks,
                           table: jnp.ndarray, points: jnp.ndarray,
                           cfg: streaming.StreamingCfg) -> jnp.ndarray:
    """RIT-order kernel output back to sample order; RIT-overflow samples
    take the reference (pixel-centric) gather on the ORIGINAL table — the
    paper's fallback, layout-independent by construction."""
    feats = scatter_rit_outputs(out_mv, blocks.samples, points.shape[0])
    return select_fallback(feats, blocks.overflow, points, cfg,
                           lambda ids, w: fallback_gather(table, ids, w))


@jax.named_scope("rit_fallback")
def select_fallback(feats: jnp.ndarray, overflow: jnp.ndarray,
                    points: jnp.ndarray, cfg: streaming.StreamingCfg,
                    gather) -> jnp.ndarray:
    """Samples whose ``overflow`` [T] is set take ``gather(ids, weights)``
    over their dense-grid corners in place of their kernel output
    ``feats`` [T, C] (the fallback is computed for every sample)."""
    gids, gw = grids.corner_ids_weights(points, cfg.grid_res)
    return jnp.where(overflow[:, None], gather(gids, gw), feats)


def gather_trilerp_ref_scened(tables: jnp.ndarray, scene: jnp.ndarray,
                              ids: jnp.ndarray, weights: jnp.ndarray
                              ) -> jnp.ndarray:
    """Per-sample-scene fallback gather over stacked dense tables
    ``[K, res^3, C]``: the same rows and the same corner sum as
    :func:`fallback_gather` on the sample's own scene's table, so a
    single-scene slice of the output is bit-identical to the exclusive
    fallback gather."""
    return grids.gather_trilerp_corners(lambda v: tables[scene, ids[:, v]],
                                        weights)


def fallback_gather(table: jnp.ndarray, ids: jnp.ndarray,
                    weights: jnp.ndarray) -> jnp.ndarray:
    """The RIT-overflow fallback: the pixel-centric gather on the ORIGINAL
    dense table ``[res^3, C]``, computed for every sample of the stage."""
    return grids.gather_trilerp_corners(lambda v: table[ids[:, v]], weights)


def _scatter_with_fallback_scened(out_mv: jnp.ndarray, blocks: _RitBlocks,
                                  tables: jnp.ndarray, scene: jnp.ndarray,
                                  points: jnp.ndarray,
                                  cfg: streaming.StreamingCfg) -> jnp.ndarray:
    """Mixed-scene :func:`_scatter_with_fallback`: the overflow fallback
    reads each sample's own scene's ORIGINAL dense table."""
    feats = scatter_rit_outputs(out_mv, blocks.samples, points.shape[0])
    return select_fallback(
        feats, blocks.overflow, points, cfg,
        lambda ids, w: gather_trilerp_ref_scened(tables, scene, ids, w))


class TickFeatures(NamedTuple):
    """The fused sweep's gathered features in sample order, plus which
    samples spilled past their RIT bucket and took the overflow fallback."""

    hole: jnp.ndarray           # [Th, C]
    ref: jnp.ndarray            # [Tr, C]
    hole_overflow: jnp.ndarray  # [Th] bool
    ref_overflow: jnp.ndarray   # [Tr] bool


def gather_features_tick_scenes(tables: jnp.ndarray, mv_tables: jnp.ndarray,
                                scene_of_seg: jnp.ndarray,
                                cfg: streaming.StreamingCfg,
                                pts_hole: jnp.ndarray, seg_hole: jnp.ndarray,
                                pts_ref: jnp.ndarray, seg_ref: jnp.ndarray, *,
                                num_seg: int, ref_cap_factor: int = 2,
                                interpret: bool | None = None
                                ) -> TickFeatures:
    """Mixed-scene :func:`gather_features_tick`: one fused sweep over the
    *resident scene set*.

    ``tables`` ``[K, res^3, C]`` / ``mv_tables`` ``[K, num_mv, P, C]`` are
    the K device-resident scene pages (K static = the engine's page
    count); ``scene_of_seg`` ``[num_seg] int32`` is the traced segment→
    page map, so scene-set churn re-steers the gather without recompiling.
    RIT bucketing stays per ``(segment, MVoxel)`` — capacity isolation is
    already per segment — and each segment's gather + overflow fallback
    read only its own scene's rows, which keeps every segment bit-
    identical to its exclusive single-scene run."""
    cfg_ref = dataclasses.replace(
        cfg, capacity=cfg.capacity * ref_cap_factor)
    bh = _rit_blocks(pts_hole, seg_hole, num_seg, cfg)
    br = _rit_blocks(pts_ref, seg_ref, num_seg, cfg_ref)
    with jax.named_scope("gather"):
        seg_tables = mv_tables[scene_of_seg]  # [num_seg, num_mv, P, C]
        out_h, out_r = fused_gather_dual_per_seg(
            seg_tables, bh.ids_mv, bh.w_mv, br.ids_mv, br.w_mv,
            num_seg=num_seg, interpret=interpret)
    scn_h = scene_of_seg[jnp.clip(seg_hole, 0, num_seg - 1)]
    scn_r = scene_of_seg[jnp.clip(seg_ref, 0, num_seg - 1)]
    feats_h = _scatter_with_fallback_scened(out_h, bh, tables, scn_h,
                                            pts_hole, cfg)
    feats_r = _scatter_with_fallback_scened(out_r, br, tables, scn_r,
                                            pts_ref, cfg)
    return TickFeatures(feats_h, feats_r, bh.overflow, br.overflow)


def gather_features_tick(table: jnp.ndarray, mv_table: jnp.ndarray,
                         cfg: streaming.StreamingCfg,
                         pts_hole: jnp.ndarray, seg_hole: jnp.ndarray,
                         pts_ref: jnp.ndarray, seg_ref: jnp.ndarray, *,
                         num_seg: int, ref_cap_factor: int = 2,
                         interpret: bool | None = None
                         ) -> TickFeatures:
    """The tick's ONE feature-gather pass: hole-fill + next-reference
    samples through a single fused MVoxel-table sweep.

    ``pts_hole``/``seg_hole`` are this tick's pooled hole samples (seg id
    ``num_seg`` = dropped padding), ``pts_ref``/``seg_ref`` the next
    tick's reference samples. The reference set is the denser stream (a
    full frame per session vs. a hole pool), so its RIT capacity scales
    by ``ref_cap_factor`` to keep the overflow-fallback rate comparable
    to the staged path's per-chunk RITs. Returns the hole features
    ``[Th, C]`` and reference features ``[Tr, C]`` in sample order, with
    each set's per-sample RIT-overflow mask (:class:`TickFeatures`).
    """
    cfg_ref = dataclasses.replace(
        cfg, capacity=cfg.capacity * ref_cap_factor)
    bh = _rit_blocks(pts_hole, seg_hole, num_seg, cfg)
    br = _rit_blocks(pts_ref, seg_ref, num_seg, cfg_ref)
    with jax.named_scope("gather"):
        out_h, out_r = fused_gather_dual(mv_table, bh.ids_mv, bh.w_mv,
                                         br.ids_mv, br.w_mv, num_seg=num_seg,
                                         interpret=interpret)
    feats_h = _scatter_with_fallback(out_h, bh, table, pts_hole, cfg)
    feats_r = _scatter_with_fallback(out_r, br, table, pts_ref, cfg)
    return TickFeatures(feats_h, feats_r, bh.overflow, br.overflow)


# ---------------------------------------------------------------------------
# analytic bytes-moved accounting (the Pallas pipeline's side of the
# per-tick bytes_moved_per_frame metric; roofline.hlo_cost derives the
# XLA/staged path's from compiled HLO)
# ---------------------------------------------------------------------------


def halo_block_bytes(cfg: streaming.StreamingCfg, channels: int,
                     bytes_per_el: int = 4) -> int:
    """HBM bytes of ONE staged MVoxel halo block under ``cfg.layout``."""
    return cfg.halo_rows * channels * bytes_per_el


def tick_traffic(cfg: streaming.StreamingCfg, channels: int, num_seg: int,
                 cap_hole: int, cap_ref: int, bytes_per_el: int = 4
                 ) -> Dict[str, float]:
    """Analytic per-tick HBM traffic of the fused streaming pipeline.

    The fused kernel runs exactly ONE sweep per tick: every halo block is
    fetched once (``mvoxel_table_bytes``); the RIT side streams — per
    (segment, MVoxel) block — ids + weights in and gathered features out
    for both stages (``rit_bytes``). These are grid-schedule constants
    (counted from the BlockSpecs, not measured), which is the point: the
    Pallas pipeline's traffic is statically known.
    """
    num_mv = cfg.num_mvoxels
    table_bytes = num_mv * halo_block_bytes(cfg, channels, bytes_per_el)
    per_slot = (cap_hole + cap_ref) * 8 * (4 + 4)  # ids int32 + weights f32
    out_bytes = (cap_hole + cap_ref) * channels * bytes_per_el
    rit_bytes = num_seg * num_mv * (per_slot + out_bytes)
    return {
        "mvoxel_table_sweeps": 1.0,
        "mvoxel_table_bytes": float(table_bytes),
        "rit_bytes": float(rit_bytes),
        "total_bytes": float(table_bytes + rit_bytes),
    }


def serving_sweeps_per_tick(total_ticks: int, admission_ticks: int,
                            prime_sweeps: float) -> float:
    """Amortized MVoxel-table sweeps per SERVING tick on the fused path.

    Every fused serving tick runs exactly one table sweep; a tick that
    admits sessions additionally pays the staged ``prime_reference``
    dispatch, whose ``lax.map`` chunks each re-stream the table once
    (``prime_sweeps`` — the engine's ``staged_ref_sweeps`` at the slot
    batch shape). Steady state (no admissions) is therefore exactly 1.0,
    and a serving run's amortized count approaches it as trajectories
    outlive their admission tick.
    """
    return 1.0 + admission_ticks * prime_sweeps / max(total_ticks, 1)
