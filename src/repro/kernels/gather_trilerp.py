"""Pallas TPU kernel: the Gathering Unit (paper §IV-B/C) adapted to TPU.

One grid step = one (MVoxel, segment) pair (the MVoxel is the paper's
streaming unit; the segment is the flat ray-batch core's per-session RIT
bucket). The MVoxel's halo feature block is staged HBM→VMEM by the Pallas
pipeline (which double-buffers — literally the paper's "standard double
buffer" §IV-A), and the RIT-assigned ray samples for that MVoxel are
processed while it is resident. Segments iterate on the *inner* grid
dimension, so one staged block serves every segment before the pipeline
advances to the next MVoxel.

TPU adaptation of the GU (DESIGN.md §2):
* sample-major lanes    → the RIT blocks and the gathered outputs put the
  sample axis on the minor (128-lane) axis of the tile: ids/weights are
  ``[8, cap]`` and outputs ``[C, cap]``. With 8 corners or C = 12 channels
  on the lane axis, the tiled layout would pad every block to 128 lanes
  (16x / ~10x the logical bytes in HBM). On top of that,
  ``StreamingCfg.layout="bank_interleaved"`` row-permutes the halo block so
  the 8 corners of every voxel hit 8 distinct SRAM banks (the paper's
  bank-conflict-free layout); ids arrive pre-remapped
  (:func:`repro.core.streaming.remap_local_ids`) and the kernel itself is
  layout-oblivious — the one-hot select works on any row order.
* crossbar-free gather  → gather-as-matmul: an 8-way one-hot select matrix
  (built with broadcasted_iota compares, no scatter/crossbar) contracted with
  the resident feature block on the MXU. The B×M trilerp reducers become one
  [P, C]ᵀ × [P, cap] matmul per corner.

Shapes:
  mv_table [num_mv, P, C]             — P halo rows, C channels
  ids      [num_seg * num_mv, 8, cap] — per-sample local row ids (pad: 0)
  weights  [num_seg * num_mv, 8, cap] — trilerp weights (pad columns: 0)
  out      [num_seg * num_mv, C, cap]

There is ONE kernel body: the unsegmented entry is simply the
``num_seg=1`` case of the segmented grid, so layout/gather changes land in
exactly one place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

# Scoped VMEM for every kernel that runs gather_block: its HIGHEST-precision
# one-hot dots at a 1024-sample RIT block need more than Mosaic's 16 MiB
# default (24 MiB in the v5e compile rehearsal); a v5e core has 128 MiB.
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 2**20)


def gather_block(tbl: jnp.ndarray, ids: jnp.ndarray, w: jnp.ndarray,
                 out_dtype) -> jnp.ndarray:
    """The GU inner loop on a VMEM-resident halo block.

    ``tbl`` [P, C], ``ids``/``w`` [8, cap] → [C, cap]. 8 statically
    unrolled corner selects (the GU's 8 cycles), each a one-hot × weight
    matmul on the MXU that contracts the halo-row axis of both operands.
    Shared by the per-stage kernel below and the fused streaming-pipeline
    kernel (kernels/streaming_pipeline.py), so every gather in the
    codebase runs this exact body. The dots run at HIGHEST precision:
    Mosaic's default for float32 operands is one bfloat16 pass, which
    misses the float32 oracle by ~3e-3 relative on a TPU v5e.
    """
    p = tbl.shape[0]
    iota_p = jax.lax.broadcasted_iota(jnp.int32, (p, 1), 0)  # [P, 1]
    acc = jnp.zeros((tbl.shape[1], ids.shape[1]), jnp.float32)
    for v in range(8):  # 8 voxel corners — static unroll (the GU's 8 cycles)
        onehot = (ids[v: v + 1, :] == iota_p).astype(jnp.float32)  # [P, cap]
        sel = onehot * w[v: v + 1, :]
        acc = acc + jax.lax.dot_general(
            tbl, sel, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)  # MXU: [C, cap]
    return acc.astype(out_dtype)


def _kernel(tbl_ref, ids_ref, w_ref, out_ref):
    out_ref[0, 0] = gather_block(tbl_ref[0], ids_ref[0, 0], w_ref[0, 0],
                                 out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_seg", "interpret"))
def gather_trilerp_mvoxels_segmented(mv_table: jnp.ndarray, ids: jnp.ndarray,
                                     weights: jnp.ndarray, *, num_seg: int,
                                     interpret: bool | None = None
                                     ) -> jnp.ndarray:
    """Segment-aware GU entry point for the flat ray-batch core.

    ``ids``/``weights`` are ``[num_seg * num_mv, 8, cap]`` — one RIT block
    per (segment, MVoxel) pair, segment-major, so every segment (= serving
    session) keeps its own per-MVoxel sample capacity exactly as an
    exclusive single-session run would. The grid iterates segments on the
    *inner* dimension: one MVoxel halo block stays resident in VMEM while
    every segment's samples for it are processed (num_seg reuses per
    HBM→VMEM stage instead of re-fetching the block per session — the
    cross-session fusion the flat core exists for).

    Returns ``[num_seg * num_mv, C, cap]`` in the same segment-major order.
    """
    interpret = resolve_interpret(interpret)
    num_mv, p, c = mv_table.shape
    cap = ids.shape[2]
    ids4 = ids.reshape(num_seg, num_mv, 8, cap)
    w4 = weights.reshape(num_seg, num_mv, 8, cap)
    out = pl.pallas_call(
        _kernel,
        grid=(num_mv, num_seg),  # seg innermost: halo block stays resident
        in_specs=[
            # stream one MVoxel halo block per outer step (auto double-
            # buffered by the Pallas grid pipeline)
            pl.BlockSpec((1, p, c), lambda m, s: (m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap), lambda m, s: (s, m, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c, cap), lambda m, s: (s, m, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_seg, num_mv, c, cap),
                                       mv_table.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(mv_table, ids4, w4)
    return out.reshape(num_seg * num_mv, c, cap)


def _kernel_per_seg(tbl_ref, ids_ref, w_ref, out_ref):
    out_ref[0, 0] = gather_block(tbl_ref[0, 0], ids_ref[0, 0], w_ref[0, 0],
                                 out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_seg", "interpret"))
def gather_trilerp_mvoxels_per_seg(mv_tables: jnp.ndarray, ids: jnp.ndarray,
                                   weights: jnp.ndarray, *, num_seg: int,
                                   interpret: bool | None = None
                                   ) -> jnp.ndarray:
    """Mixed-scene GU entry point: every segment brings its OWN halo table.

    ``mv_tables`` is ``[num_seg, num_mv, P, C]`` — segment ``s``'s rows are
    its scene's re-laid MVoxel table (gathered from the stacked resident
    set by the caller via the traced segment→scene map). The grid and the
    per-(segment, MVoxel) RIT blocks match
    :func:`gather_trilerp_mvoxels_segmented` exactly; only the table
    BlockSpec walks the leading scene-selected axis, so the staged block
    for grid step ``(m, s)`` holds the same rows segment ``s``'s exclusive
    single-scene run would stage — :func:`gather_block` then computes
    bit-identical outputs. Segments sharing a scene should be adjacent
    (the serve engine sorts slots scene-major) so consecutive inner steps
    reuse the staged block: one pass over the *distinct* resident tables
    per tick, Potamoi's singular-sweep property for mixed batches.
    """
    interpret = resolve_interpret(interpret)
    _, num_mv, p, c = mv_tables.shape
    cap = ids.shape[2]
    ids4 = ids.reshape(num_seg, num_mv, 8, cap)
    w4 = weights.reshape(num_seg, num_mv, 8, cap)
    out = pl.pallas_call(
        _kernel_per_seg,
        grid=(num_mv, num_seg),  # seg innermost: scene-adjacent reuse
        in_specs=[
            pl.BlockSpec((1, 1, p, c), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap), lambda m, s: (s, m, 0, 0)),
            pl.BlockSpec((1, 1, 8, cap), lambda m, s: (s, m, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c, cap), lambda m, s: (s, m, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_seg, num_mv, c, cap),
                                       mv_tables.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(mv_tables, ids4, w4)
    return out.reshape(num_seg * num_mv, c, cap)


def gather_trilerp_mvoxels(mv_table: jnp.ndarray, ids: jnp.ndarray,
                           weights: jnp.ndarray, *,
                           interpret: bool | None = None) -> jnp.ndarray:
    """Run the GU kernel over all MVoxels — the ``num_seg=1`` case of the
    segmented grid (same compiled body). Returns [num_mv, C, cap]."""
    return gather_trilerp_mvoxels_segmented(mv_table, ids, weights,
                                            num_seg=1, interpret=interpret)
