"""The unified streaming render pipeline's Pallas stage (ROADMAP item 4).

The staged tick runs reference render and pooled hole-fill as separate
programs, and each ``lax.map`` ray chunk inside them re-streams the
MVoxel halo table HBM→VMEM (one ``pallas_call`` sweep per chunk).
Potamoi's point — and this module's job — is to collapse that into ONE
sweep per tick: the tick's pooled hole samples and the NEXT tick's
reference samples are merged into one ragged Ray Index Table
(:func:`repro.core.streaming.build_rit`), and a single kernel call
(:func:`fused_gather_dual`) gathers both sets while each halo block is
resident. Each MVoxel's feature block is therefore fetched once per tick
instead of once per ray-chunk per stage.

Every gather path runs the same steps (:func:`stream_gather`): key each
sample by its MVoxel (``page * num_mv + mv`` over a stacked scene set),
build the ragged RIT, lay the corner ids and weights out as ``[n_blocks,
8, T]`` blocks (:func:`rit_sample_blocks`), run the one ragged kernel
(:func:`repro.kernels.gather_trilerp.gather_blocks`), and take each
sample's row back with one gather (:func:`unpermute_rit_outputs`). Every
sample streams; nothing falls back to a dense-table gather.

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

import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import streaming
from repro.kernels import gather_trilerp as _gt


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_gather_dual(mv_table: jnp.ndarray, block_key: jnp.ndarray,
                      n_live: jnp.ndarray, ids: jnp.ndarray, w: jnp.ndarray,
                      *, interpret: bool | None = None) -> jnp.ndarray:
    """One MVoxel-table sweep serving BOTH tick stages: the ragged RIT of
    this tick's hole samples merged with the next reference's samples.
    The same kernel as :func:`repro.kernels.gather_trilerp.
    gather_trilerp_mvoxels_segmented`, under its own name so a profile
    tells the tick's sweep from the prime's. Returns ``[n_blocks, C, T]``.
    """
    return _gt.gather_blocks(mv_table, block_key, n_live, ids, w,
                             interpret=interpret)


def _stream_keys(points: jnp.ndarray, cfg: streaming.StreamingCfg,
                seg: Optional[jnp.ndarray] = None, num_seg: int = 1,
                scene_of_seg: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Each sample's ragged-RIT key: its MVoxel, or ``page * num_mv + mv``
    when ``scene_of_seg`` maps segments to pages of a stacked table.
    Chunk padding (``seg >= num_seg``) takes a key past every table row,
    so :func:`repro.core.streaming.build_rit` drops it."""
    key = streaming.mvoxel_ids(points, cfg)
    if seg is None:
        return key
    if scene_of_seg is not None:
        page = scene_of_seg[jnp.clip(seg, 0, num_seg - 1)]
        key = page * cfg.num_mvoxels + key
    return jnp.where(seg < num_seg, key, jnp.iinfo(jnp.int32).max)


def rit_sample_blocks(points: jnp.ndarray, rit: streaming.RIT,
                      cfg: streaming.StreamingCfg
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Corner ids (layout-remapped) and weights of every RIT column,
    ``[n_blocks, 8, T]`` with the sample axis on lanes; pad columns get
    id 0 and weight 0."""
    n_blocks, t = rit.sample.shape
    held = (rit.sample >= 0)[..., None]
    pts = points[jnp.maximum(rit.sample, 0)].reshape(-1, 3)
    local_ids, w = streaming.local_corner_ids(pts, cfg)
    local_ids = streaming.remap_local_ids(local_ids, cfg)
    ids = jnp.where(held, local_ids.reshape(n_blocks, t, 8), 0)
    w = jnp.where(held, w.reshape(n_blocks, t, 8), 0.0)
    return jnp.swapaxes(ids, 1, 2), jnp.swapaxes(w, 1, 2)


@jax.named_scope("rit_scatter")
def unpermute_rit_outputs(out: jnp.ndarray, col: jnp.ndarray
                          ) -> jnp.ndarray:
    """Kernel output ``[n_blocks, C, T]`` back to sample order ``[S, C]``:
    one gather of each sample's column. Samples the RIT does not hold
    (chunk padding) read zero."""
    n_blocks, c, t = out.shape
    rows = jnp.swapaxes(out, 1, 2).reshape(-1, c)
    # samples the RIT does not hold read zero; the stage reader counts
    # this mask as ``rit_fallback``
    with jax.named_scope("rit_fallback"):
        held = (col < n_blocks * t)[:, None]
    return jnp.where(held, rows[jnp.minimum(col, n_blocks * t - 1)], 0)


def stream_gather(kernel: Callable, mv_table: jnp.ndarray,
                  points: jnp.ndarray, cfg: streaming.StreamingCfg,
                  seg: Optional[jnp.ndarray] = None, num_seg: int = 1,
                  scene_of_seg: Optional[jnp.ndarray] = None, *,
                  interpret: bool | None = None
                  ) -> Tuple[jnp.ndarray, streaming.RIT]:
    """Samples → ragged RIT → one ``kernel`` sweep. ``mv_table`` is
    ``[num_mv, P, C]``, or with ``scene_of_seg`` a stacked ``[K, num_mv,
    P, C]`` set (:func:`_stream_keys`). Returns the kernel's ``[n_blocks,
    C, T]`` output and the RIT, whose ``col`` takes it back to sample
    order (:func:`unpermute_rit_outputs`)."""
    p, c = mv_table.shape[-2:]
    mv_table = mv_table.reshape(-1, p, c)
    with jax.named_scope("rit_build"):
        key = _stream_keys(points, cfg, seg, num_seg, scene_of_seg)
        rit = streaming.build_rit(key, mv_table.shape[0], cfg.capacity)
        ids, w = rit_sample_blocks(points, rit, cfg)
    with jax.named_scope("gather"):
        out = kernel(mv_table, rit.block_key, rit.n_live, ids, w,
                     interpret=interpret)
    return out, rit


class TickFeatures(NamedTuple):
    """The fused sweep's gathered features in sample order, and the
    padding of its ragged RIT."""

    hole: jnp.ndarray      # [Th, C]
    ref: jnp.ndarray       # [Tr, C]
    pad_columns: jnp.ndarray  # [] int32 — pad columns in live blocks
    columns: jnp.ndarray      # [] int32 — columns in live blocks


def gather_features_tick(mv_table: jnp.ndarray, cfg: streaming.StreamingCfg,
                         pts_hole: jnp.ndarray, seg_hole: jnp.ndarray,
                         pts_ref: jnp.ndarray, seg_ref: jnp.ndarray, *,
                         num_seg: int,
                         scene_of_seg: Optional[jnp.ndarray] = None,
                         interpret: bool | None = None) -> TickFeatures:
    """The tick's ONE feature-gather pass: hole-fill + next-reference
    samples through a single fused MVoxel-table sweep.

    ``pts_hole``/``seg_hole`` are this tick's pooled hole samples (seg id
    ``num_seg`` = dropped padding), ``pts_ref``/``seg_ref`` the next
    tick's reference samples. Both sets merge into one stream: one sort,
    one :func:`fused_gather_dual` call, and one gather back per set.
    ``scene_of_seg`` ``[num_seg] int32`` (with ``mv_table`` the stacked
    resident set ``[K, num_mv, P, C]``) is the mixed-scene path: each
    segment reads its own page, and each output column depends only on its
    own ids, weights and block, so a segment's features are bit-identical
    to its exclusive single-scene run. Returns the hole features ``[Th,
    C]`` and reference features ``[Tr, C]`` in sample order, and the RIT's
    padding (:class:`TickFeatures`).
    """
    th = pts_hole.shape[0]
    pts = jnp.concatenate([pts_hole, pts_ref])
    seg = jnp.concatenate([seg_hole, seg_ref])
    out, rit = stream_gather(fused_gather_dual, mv_table, pts, cfg, seg,
                             num_seg, scene_of_seg, interpret=interpret)
    columns = rit.n_live[0] * cfg.capacity
    return TickFeatures(unpermute_rit_outputs(out, rit.col[:th]),
                        unpermute_rit_outputs(out, rit.col[th:]),
                        columns - rit.live, columns)


# ---------------------------------------------------------------------------
# analytic bytes-moved accounting (the Pallas pipeline's side of the
# per-tick bytes_moved_per_frame metric; roofline.hlo_cost derives the
# XLA/staged path's from compiled HLO)
# ---------------------------------------------------------------------------


def halo_block_bytes(cfg: streaming.StreamingCfg, channels: int,
                     bytes_per_el: int = 4) -> int:
    """HBM bytes of ONE staged MVoxel halo block under ``cfg.layout``."""
    return cfg.halo_rows * channels * bytes_per_el


def tick_traffic(cfg: streaming.StreamingCfg, channels: int, samples: int,
                 bytes_per_el: int = 4) -> Dict[str, float]:
    """Analytic per-tick HBM traffic of the fused streaming pipeline.

    The fused kernel runs exactly ONE sweep per tick: every halo block is
    fetched at most once (``mvoxel_table_bytes``); the RIT side streams —
    per ragged block of ``cfg.capacity`` columns — ids + weights in and
    gathered features out for the tick's merged stream of ``samples``
    hole and reference samples (``rit_bytes``). The ragged RIT's block
    count is bounded by ``streaming.rit_num_blocks``, which is what is
    counted here: an upper bound from the kernel's static grid, not a
    measurement.
    """
    num_mv = cfg.num_mvoxels
    table_bytes = num_mv * halo_block_bytes(cfg, channels, bytes_per_el)
    columns = (streaming.rit_num_blocks(samples, num_mv, cfg.capacity)
               * cfg.capacity)
    per_column = 8 * (4 + 4) + channels * bytes_per_el  # ids, weights, out
    rit_bytes = columns * per_column
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
