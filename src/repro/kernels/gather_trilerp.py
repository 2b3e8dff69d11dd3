"""Pallas TPU kernel: the Gathering Unit (paper §IV-B/C) adapted to TPU.

One grid step = one block of the ragged Ray Index Table
(:func:`repro.core.streaming.build_rit`): up to ``T`` samples of one key
(an MVoxel, or a page's MVoxel on the mixed-scene path). Samples arrive
sorted by key, so the blocks of one MVoxel are consecutive. The halo
block's ``index_map`` reads the block's key from the scalar-prefetched
``block_key``, and the Pallas pipeline (which double-buffers — literally
the paper's "standard double buffer" §IV-A) fetches a halo block only when
the key changes: the table is swept once, in MVoxel order, and an MVoxel
that no sample lands in is never fetched. Blocks past the live count
repeat the last live block's indices, so they start no DMA, and the body
skips them.

TPU adaptation of the GU (DESIGN.md §2):
* sample-major lanes    → the RIT blocks and the gathered outputs put the
  sample axis on the minor (128-lane) axis of the tile: ids/weights are
  ``[8, T]`` and outputs ``[C, T]``. With 8 corners or C = 12 channels
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
  [P, C]ᵀ × [P, T] matmul per corner.

Shapes:
  mv_table  [num_keys, P, C]  — P halo rows, C channels
  block_key [n_blocks] int32  — non-decreasing key per block
  n_live    [1] int32         — blocks that hold samples
  ids       [n_blocks, 8, T]  — per-sample local row ids (pad: 0)
  weights   [n_blocks, 8, T]  — trilerp weights (pad columns: 0)
  out       [n_blocks, C, T]  — blocks past ``n_live`` are left unwritten

There is ONE kernel body and one ``pallas_call`` builder
(:func:`gather_blocks`); each caller's jitted wrapper only gives the
profile's instruction its name.
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
# A v5e core's scalar memory, which holds the scalar-prefetched block keys.
SMEM_BYTES = 2**20


def gather_block(tbl: jnp.ndarray, ids: jnp.ndarray, w: jnp.ndarray,
                 out_dtype) -> jnp.ndarray:
    """The GU inner loop on a VMEM-resident halo block.

    ``tbl`` [P, C], ``ids``/``w`` [8, T] → [C, T]. 8 statically
    unrolled corner selects (the GU's 8 cycles), each a one-hot × weight
    matmul on the MXU that contracts the halo-row axis of both operands.
    Each output column depends only on its own ids and weights. The dots
    run at HIGHEST precision: Mosaic's default for float32 operands is one
    bfloat16 pass, which misses the float32 oracle by ~3e-3 relative on a
    TPU v5e.
    """
    p = tbl.shape[0]
    iota_p = jax.lax.broadcasted_iota(jnp.int32, (p, 1), 0)  # [P, 1]
    acc = jnp.zeros((tbl.shape[1], ids.shape[1]), jnp.float32)
    for v in range(8):  # 8 voxel corners — static unroll (the GU's 8 cycles)
        onehot = (ids[v: v + 1, :] == iota_p).astype(jnp.float32)  # [P, T]
        sel = onehot * w[v: v + 1, :]
        acc = acc + jax.lax.dot_general(
            tbl, sel, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)  # MXU: [C, T]
    return acc.astype(out_dtype)


def _kernel(key_ref, live_ref, tbl_ref, ids_ref, w_ref, out_ref):
    del key_ref  # read by the index maps only

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        out_ref[0] = gather_block(tbl_ref[0], ids_ref[0], w_ref[0],
                                  out_ref.dtype)


def gather_blocks(mv_table: jnp.ndarray, block_key: jnp.ndarray,
                  n_live: jnp.ndarray, ids: jnp.ndarray,
                  weights: jnp.ndarray, *,
                  interpret: bool | None = None) -> jnp.ndarray:
    """The ragged GU sweep: grid ``(n_blocks,)``, block ``b`` gathers its
    ``T`` columns from halo block ``mv_table[block_key[b]]``. Shapes as
    in the module docstring. Not jitted, so the ``pallas_call`` takes the
    name of the caller's jitted wrapper."""
    _, p, c = mv_table.shape
    n_blocks, _, t = ids.shape
    # each scalar-prefetch operand is allocated in 512-byte granules
    smem = sum(-(-4 * a.size // 512) * 512 for a in (block_key, n_live))
    if smem > SMEM_BYTES:
        raise ValueError(f"{n_blocks} RIT blocks need {smem} bytes of "
                         f"scalar memory; a v5e core has {SMEM_BYTES}")

    def block(b, key, live):
        # dead blocks repeat the last live block: no DMA in or out
        return (jnp.minimum(b, jnp.maximum(live[0] - 1, 0)), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[
            # the block's MVoxel; consecutive blocks of one key reuse it
            pl.BlockSpec((1, p, c), lambda b, key, live: (key[b], 0, 0)),
            pl.BlockSpec((1, 8, t), block),
            pl.BlockSpec((1, 8, t), block),
        ],
        out_specs=pl.BlockSpec((1, c, t), block),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, c, t), mv_table.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=resolve_interpret(interpret),
    )(block_key, n_live, mv_table, ids, weights)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_trilerp_mvoxels_segmented(mv_table: jnp.ndarray,
                                     block_key: jnp.ndarray,
                                     n_live: jnp.ndarray, ids: jnp.ndarray,
                                     weights: jnp.ndarray, *,
                                     interpret: bool | None = None
                                     ) -> jnp.ndarray:
    """The staged path's and the admission prime's GU sweep over one
    ragged RIT (:func:`gather_blocks`); the segments of the flat ray-batch
    core share its blocks. Returns ``[n_blocks, C, T]``."""
    return gather_blocks(mv_table, block_key, n_live, ids, weights,
                         interpret=interpret)
