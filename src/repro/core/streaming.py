"""Fully-streaming (memory-centric) NeRF rendering (paper §IV-A).

Pixel-centric rendering walks ray samples in image order → irregular DRAM
access. Memory-centric rendering walks *MVoxels* (blocks of voxel vertices,
paper: 8×8×8 points) in DRAM layout order and processes whichever ray samples
live in the resident MVoxel. Ray samples are statically known, so the reorder
is a single global sort per frame (the paper's key observation vs. ray-tracing
reordering).

Pieces:
* ``mvoxel_ids``          — sample → MVoxel assignment (base-corner rule).
* ``build_rit``           — ragged Ray Index Table: samples sorted by
                            MVoxel, each MVoxel's run cut into blocks of
                            ``capacity`` columns; every sample streams,
                            none falls back.
* ``build_mvoxel_table``  — re-lays the vertex table as contiguous per-MVoxel
                            halo blocks [(edge+1)^3, C] — "vertex features
                            within one MVoxel stored continuously in DRAM".
* ``streaming_gather``    — sorted-order gather (bit-identical to the
                            pixel-centric gather; permutation invariance is
                            the correctness contract, tested).
* ``access_trace`` / cache + streaming statistics for the cost model and the
  Fig. 4/5 reproductions.

Hot-path wiring: ``NerfModel`` with ``backend="streaming"`` routes
``query_features`` through ``kernels.ops.gather_features_streaming`` (the
Pallas GU kernel over these RIT/MVoxel structures); ``build_mvoxel_table``
is hoisted out of the frame loop by ``NerfModel.prepare_streaming`` and
cached per params, so the device-resident engine pays the re-layout once
per table, not once per frame.
"""
from __future__ import annotations

import functools as _functools
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.nerf import grids


@dataclass(frozen=True)
class StreamingCfg:
    grid_res: int = 64  # vertices per scene edge
    mvoxel_edge: int = 8  # vertices per MVoxel edge (paper: 8^3 points)
    capacity: int = 512  # samples per RIT block (an MVoxel may fill several)
    # on-chip layout of the staged halo block (paper §on-chip data layout):
    # "identity" keeps halo points x-major; "bank_interleaved" places each
    # point so the 8 corners of every voxel hit 8 distinct SRAM banks.
    # The re-layout is a pure row permutation (plus zero pad rows), so
    # gathered features are bit-identical across layouts.
    layout: str = "identity"
    num_banks: int = 8  # SRAM banks the interleave targets (paper: 8 reducers)

    @property
    def mv_per_edge(self) -> int:
        return (self.grid_res + self.mvoxel_edge - 1) // self.mvoxel_edge

    @property
    def num_mvoxels(self) -> int:
        return self.mv_per_edge**3

    @property
    def halo_points(self) -> int:
        return (self.mvoxel_edge + 1) ** 3

    @property
    def halo_rows(self) -> int:
        """Rows of the staged halo block under this layout (identity: the
        halo point count; bank_interleaved: padded so every bank owns an
        equal stride of rows)."""
        if self.layout == "identity":
            return self.halo_points
        return layout_row_map(self)[1]


def sample_base_coords(points: jnp.ndarray, res: int) -> jnp.ndarray:
    """Integer base-corner coordinates of each sample's voxel. [S,3] int32."""
    g = (points + 1.0) * 0.5 * (res - 1)
    g = jnp.clip(g, 0.0, res - 1 - 1e-4)
    return jnp.floor(g).astype(jnp.int32)


def mvoxel_ids(points: jnp.ndarray, cfg: StreamingCfg) -> jnp.ndarray:
    """MVoxel id per sample (x-major over MVoxel grid). [S] int32."""
    base = sample_base_coords(points, cfg.grid_res)
    mv = base // cfg.mvoxel_edge
    m = cfg.mv_per_edge
    return (mv[:, 0] * m + mv[:, 1]) * m + mv[:, 2]


def local_corner_ids(points: jnp.ndarray, cfg: StreamingCfg
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Corner indices *within the sample's MVoxel halo block* + weights.

    Returns (local_ids [S,8] in [0, (edge+1)^3), weights [S,8]).
    """
    res, e = cfg.grid_res, cfg.mvoxel_edge
    g = (points + 1.0) * 0.5 * (res - 1)
    g = jnp.clip(g, 0.0, res - 1 - 1e-4)
    base = jnp.floor(g).astype(jnp.int32)
    frac = g - base
    local = base % e  # position inside mvoxel, in [0, e)
    corners = local[:, None, :] + grids._CORNERS[None, :, :]  # [S,8,3] in [0, e]
    p = e + 1
    ids = (corners[..., 0] * p + corners[..., 1]) * p + corners[..., 2]
    cw = jnp.where(grids._CORNERS[None, :, :] == 1, frac[:, None, :], 1.0 - frac[:, None, :])
    return ids, cw.prod(axis=-1)


# ---------------------------------------------------------------------------
# on-chip halo-block layout (paper §on-chip data layout: bank interleaving)
# ---------------------------------------------------------------------------


def halo_point_banks(cfg: StreamingCfg) -> np.ndarray:
    """Target SRAM bank per halo point, [(edge+1)^3] int.

    With ``num_banks = 8`` the bank of point ``(x, y, z)`` is
    ``(4x + 2y + z) mod 8`` — the 8 corners of ANY voxel (offsets
    ``(a, b, c)`` with a,b,c ∈ {0,1}) differ by ``4a + 2b + c``, which
    takes all 8 residues, so every trilerp's concurrent corner reads hit
    8 distinct banks (the paper's conflict-free reducer feed).
    """
    p = cfg.mvoxel_edge + 1
    x, y, z = np.meshgrid(np.arange(p), np.arange(p), np.arange(p),
                          indexing="ij")
    return ((4 * x + 2 * y + z) % cfg.num_banks).reshape(-1)


@_functools.lru_cache(maxsize=None)
def layout_row_map(cfg: StreamingCfg) -> Tuple[np.ndarray, int]:
    """(row_of_point [(edge+1)^3] int32, padded row count) for the
    bank-interleaved layout.

    Point ``p`` is stored at row ``rank_within_bank(p) * num_banks +
    bank(p)`` — row index mod ``num_banks`` IS the bank, so the physical
    row stream round-robins the banks and the 8 corners of every voxel
    (8 distinct target banks) occupy 8 distinct banks by construction.
    Banks own unequal point counts, so rows pad up to
    ``num_banks * max_bank_count`` (pad rows are zero and never selected
    — the gather is a one-hot matmul over remapped ids).
    """
    banks = halo_point_banks(cfg)
    b = cfg.num_banks
    rank = np.zeros_like(banks)
    for bank in range(b):
        sel = banks == bank
        rank[sel] = np.arange(int(sel.sum()))
    rows = (rank * b + banks).astype(np.int32)
    padded = b * int(np.bincount(banks, minlength=b).max())
    return rows, padded


def apply_layout(mv_table: jnp.ndarray, cfg: StreamingCfg) -> jnp.ndarray:
    """Re-lay the halo blocks ``[num_mv, P, C]`` for ``cfg.layout``.

    Identity: returned unchanged. Bank-interleaved: rows scatter to their
    bank-interleaved positions (``[num_mv, halo_rows, C]``, zero padding).
    A pure value-preserving permutation — gathered features stay
    bit-identical because the one-hot select contributes exactly one
    nonzero product per corner regardless of row order.
    """
    if cfg.layout == "identity":
        return mv_table
    rows, padded = layout_row_map(cfg)
    num_mv, p, c = mv_table.shape
    out = jnp.zeros((num_mv, padded, c), mv_table.dtype)
    return out.at[:, jnp.asarray(rows)].set(mv_table)


def remap_local_ids(local_ids: jnp.ndarray, cfg: StreamingCfg) -> jnp.ndarray:
    """Map x-major local corner ids to the layout's physical rows."""
    if cfg.layout == "identity":
        return local_ids
    rows, _ = layout_row_map(cfg)
    return jnp.asarray(rows)[local_ids]


def bank_conflict_factor(cfg: StreamingCfg) -> float:
    """Mean SRAM-bank serialization of one trilerp's 8 concurrent corner
    reads (1.0 = conflict-free; k = worst bank serves k corners).

    Rows interleave across ``num_banks`` banks (bank = row mod banks);
    averaged over every voxel base in the halo block. The identity
    (x-major) layout collides because corner offsets ``{1, edge+1,
    (edge+1)^2, ...}`` share residues mod 8; the interleaved layout is
    1.0 by construction.
    """
    e, p, b = cfg.mvoxel_edge, cfg.mvoxel_edge + 1, cfg.num_banks
    if cfg.layout == "identity":
        row_of = np.arange(p**3, dtype=np.int64)
    else:
        row_of = layout_row_map(cfg)[0].astype(np.int64)
    base = np.stack(np.meshgrid(np.arange(e), np.arange(e), np.arange(e),
                                indexing="ij"), -1).reshape(-1, 3)
    corners = base[:, None, :] + np.asarray(grids._CORNERS)[None, :, :]
    ids = (corners[..., 0] * p + corners[..., 1]) * p + corners[..., 2]
    bank = row_of[ids] % b  # [voxels, 8]
    worst = np.array([np.bincount(row, minlength=b).max() for row in bank])
    return float(worst.mean())


def build_mvoxel_table(table: jnp.ndarray, cfg: StreamingCfg) -> jnp.ndarray:
    """Global vertex table [res^3, C] -> per-MVoxel halo blocks
    [num_mv, (edge+1)^3, C], contiguous in DRAM order (x-major MVoxel walk).
    ``cfg.layout`` then re-lays each block's rows on-chip-bank-interleaved
    (see :func:`apply_layout`); local corner ids must be remapped through
    :func:`remap_local_ids` to match."""
    res, e, m = cfg.grid_res, cfg.mvoxel_edge, cfg.mv_per_edge
    p = e + 1
    grid = table.reshape(res, res, res, -1)
    # pad so every halo block is full even at the boundary
    pad = m * e + 1 - res
    grid = jnp.pad(grid, ((0, pad), (0, pad), (0, pad), (0, 0)), mode="edge")
    idx = jnp.arange(m) * e
    # vectorized extraction via gather of start indices
    starts = jnp.stack(jnp.meshgrid(idx, idx, idx, indexing="ij"), -1).reshape(-1, 3)

    def extract(s):
        return jax.lax.dynamic_slice(grid, (s[0], s[1], s[2], 0),
                                     (p, p, p, grid.shape[-1]))

    blocks = jax.vmap(extract)(starts)  # [num_mv, p, p, p, C]
    return apply_layout(blocks.reshape(cfg.num_mvoxels, p**3, -1), cfg)


class RIT(NamedTuple):
    """Ragged Ray Index Table: samples in key (MVoxel) order, each key's
    run cut into blocks of ``T`` columns (``T = cfg.capacity``)."""

    block_key: jnp.ndarray  # [n_blocks] int32, non-decreasing
    n_live: jnp.ndarray     # [1] int32 — blocks that hold samples
    sample: jnp.ndarray     # [n_blocks, T] int32 sample per column (-1 pad)
    col: jnp.ndarray        # [S] int32 column per sample (dropped: n_blocks*T)
    live: jnp.ndarray       # [] int32 — samples the table holds


def rit_num_blocks(num_samples: int, num_keys: int, block: int) -> int:
    """Static block count of a ragged RIT: the worst case, since each
    non-empty key's run rounds up by less than one block and at most
    ``min(num_keys, num_samples)`` keys are non-empty."""
    return max(-(-num_samples // block) + min(num_keys, num_samples), 1)


def build_rit(key: jnp.ndarray, num_keys: int, block: int) -> RIT:
    """Ragged RIT over ``num_keys`` keys (MVoxels, or ``page * num_mv +
    mv`` over a stacked scene set), ``block`` columns per block.

    One stable sort of (key, sample) puts the samples in key order; each
    key's run fills ``ceil(count / block)`` consecutive blocks, so no
    sample spills whatever piles into one MVoxel. Samples whose key is
    ``>= num_keys`` (chunk-padding rays routed to the dump segment) are
    dropped: they take no column. Blocks past ``n_live`` repeat the last
    live key, so a kernel that walks the blocks fetches nothing new there.
    """
    s = key.shape[0]
    n_blocks = rit_num_blocks(s, num_keys, block)
    key = jnp.minimum(key, num_keys).astype(jnp.int32)
    key_sorted, order = jax.lax.sort(
        (key, jnp.arange(s, dtype=jnp.int32)), num_keys=1, is_stable=True)
    # key k's run in the sorted order is [start[k], start[k + 1])
    start = jnp.searchsorted(
        key_sorted, jnp.arange(num_keys + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    blocks = (start[1:] - start[:-1] + block - 1) // block
    ends = jnp.cumsum(blocks, dtype=jnp.int32)
    first = ends - blocks
    n_live = ends[-1]
    k = jnp.minimum(key_sorted, num_keys - 1)
    col_sorted = jnp.where(
        key_sorted < num_keys,
        first[k] * block + jnp.arange(s, dtype=jnp.int32) - start[k],
        n_blocks * block)
    col = jnp.zeros((s,), jnp.int32).at[order].set(col_sorted,
                                                   unique_indices=True)
    b = jnp.arange(n_blocks, dtype=jnp.int32)
    bk = jnp.searchsorted(ends, b, side="right").astype(jnp.int32)
    block_key = jnp.minimum(bk, jnp.minimum(bk[jnp.maximum(n_live - 1, 0)],
                                            num_keys - 1))
    # sorted position of every column; past its key's run it is a pad
    row = (start[block_key] + (b - first[block_key]) * block)[:, None] \
        + jnp.arange(block, dtype=jnp.int32)[None, :]
    held = row < start[block_key + 1][:, None]
    sample = jnp.where(held, order[jnp.clip(row, 0, s - 1)], -1)
    return RIT(block_key, n_live.reshape(1), sample, col, start[num_keys])


def streaming_gather(table: jnp.ndarray, points: jnp.ndarray,
                     cfg: StreamingCfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Memory-centric feature gather: process samples in MVoxel-sorted order.

    Returns (features [S, C], order [S]). Numerically identical to the
    pixel-centric gather (tested); the *order* is what changes the DRAM trace.
    """
    mv = mvoxel_ids(points, cfg)
    order = jnp.argsort(mv)
    pts_sorted = points[order]
    ids, w = grids.corner_ids_weights(pts_sorted, cfg.grid_res)
    feats_sorted = grids.gather_trilerp_ref(table, ids, w)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    return feats_sorted[inv], order


# ---------------------------------------------------------------------------
# DRAM / cache statistics (feeds costmodel + Fig. 4/5 reproductions)
# ---------------------------------------------------------------------------


def vertex_access_stream(points: np.ndarray, res: int) -> np.ndarray:
    """Vertex ids in pixel-centric access order (8 per sample). [S*8]."""
    ids, _ = grids.corner_ids_weights(jnp.asarray(points), res)
    return np.asarray(ids).reshape(-1)


def lru_cache_stats(addresses: np.ndarray, cache_lines: int,
                    line_addrs: int = 8) -> Dict[str, float]:
    """LRU cache simulation at line granularity.

    addresses: vertex ids in access order; a line holds ``line_addrs``
    consecutive vertices. Returns miss rate + streaming fraction (fraction of
    consecutive *DRAM* fetches whose line address is sequential).
    """
    lines = addresses // line_addrs
    lru: OrderedDict[int, None] = OrderedDict()
    misses = 0
    seq = 0
    last_fetch = -(10**9)
    for ln in lines.tolist():
        if ln in lru:
            lru.move_to_end(ln)
            continue
        misses += 1
        if ln == last_fetch + 1:
            seq += 1
        last_fetch = ln
        lru[ln] = None
        if len(lru) > cache_lines:
            lru.popitem(last=False)
    total = len(lines)
    return {
        "accesses": float(total),
        "miss_rate": misses / max(total, 1),
        "dram_fetches": float(misses),
        "streaming_fraction": seq / max(misses, 1),
        "non_streaming_fraction": 1.0 - seq / max(misses, 1),
    }


def streaming_traffic(mv: np.ndarray, cfg: StreamingCfg, channels: int,
                      bytes_per_el: int = 4) -> Dict[str, float]:
    """DRAM traffic of the fully-streaming walk: each *touched* MVoxel halo
    block is fetched exactly once, sequentially."""
    touched = np.unique(np.asarray(mv))
    block_bytes = cfg.halo_points * channels * bytes_per_el
    return {
        "mvoxels_touched": float(len(touched)),
        "bytes": float(len(touched) * block_bytes),
        "streaming_fraction": 1.0,
        "non_streaming_fraction": 0.0,
    }


def pixel_centric_traffic(points: np.ndarray, res: int, channels: int,
                          cache_bytes: int = 2 * 2**20,
                          bytes_per_el: int = 4) -> Dict[str, float]:
    """Pixel-centric DRAM traffic through a small on-chip cache (paper: 2 MB)."""
    stream = vertex_access_stream(points, res)
    line_addrs = 8
    line_bytes = line_addrs * channels * bytes_per_el
    stats = lru_cache_stats(stream, cache_lines=max(cache_bytes // line_bytes, 1),
                            line_addrs=line_addrs)
    stats["bytes"] = stats["dram_fetches"] * line_bytes
    return stats
