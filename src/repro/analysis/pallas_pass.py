"""Pallas kernel pass: BlockSpec/grid/VMEM/bank-layout validation.

Instead of re-deriving each kernel's launch geometry from source (which
drifts), this pass *captures the real thing*: it patches
``pl.pallas_call`` with a recording spy and traces every kernel wrapper
under ``jax.eval_shape`` at representative shapes — the wrapper's own
shape math runs, the recorded ``grid``/``in_specs``/``out_specs``/
``scratch_shapes`` are exactly what a device launch would get, and nothing
executes (the spy returns zeros of ``out_shape``).

Rules:

- ``pallas-block-divisibility``  every BlockSpec block dim must divide its
                                 operand dim (the repo's kernels guarantee
                                 this by ``round_up`` padding in ops.py —
                                 a non-dividing block silently truncates
                                 or over-reads on a real accelerator).
- ``pallas-vmem-budget``         analytic per-launch VMEM footprint:
                                 Σ block bytes (in + out, ×2 for the grid
                                 pipeline's double buffering) + scratch
                                 ≤ 16 MiB (the per-core VMEM in the
                                 accelerator guide).
- ``mvoxel-bank-conflict``       recompute the SRAM bank-conflict factor
                                 of every registered ``mvoxel_layout``
                                 from its row permutation (independent of
                                 ``streaming.bank_conflict_factor``):
                                 ``bank_interleaved`` must be exactly 1.0
                                 and a true permutation; ``identity``'s
                                 known 3.0 is recorded, not gated.
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.analysis.findings import Finding

VMEM_BUDGET_BYTES = 16 * 2**20  # ~16 MB/core (guide: TPU VMEM)
DOUBLE_BUFFER = 2  # grid pipeline overlaps fetch of block i+1 with compute

ALL_RULES = ("pallas-block-divisibility", "pallas-vmem-budget",
             "mvoxel-bank-conflict")


@dataclasses.dataclass
class LaunchRecord:
    """One captured ``pallas_call`` launch: geometry + operand avals."""

    kernel_name: str
    path: str
    line: int
    grid: Tuple[int, ...]
    in_blocks: List[Tuple[Tuple[int, ...], Tuple[int, ...], int]]
    #            (block_shape, operand_shape, block_bytes)
    out_blocks: List[Tuple[Tuple[int, ...], Tuple[int, ...], int]]
    scratch_bytes: int

    @property
    def vmem_bytes(self) -> int:
        blocks = sum(b for _, _, b in self.in_blocks + self.out_blocks)
        return blocks * DOUBLE_BUFFER + self.scratch_bytes


def _as_seq(x) -> Sequence:
    if x is None:
        return []
    return x if isinstance(x, (list, tuple)) else [x]


def _block_bytes(block_shape, dtype) -> int:
    n = 1
    for d in block_shape:
        n *= (1 if d is None else int(d))
    return n * np.dtype(dtype).itemsize


def _anchor(fn: Callable) -> Tuple[str, int]:
    """(repo-relative-ish path, line) of a wrapper function."""
    raw = inspect.unwrap(fn)
    try:
        path = inspect.getsourcefile(raw) or "<unknown>"
        line = inspect.getsourcelines(raw)[1]
    except (OSError, TypeError):
        path, line = "<unknown>", 1
    return path, line


def record_launches(fn: Callable, *args, **kwargs) -> List[LaunchRecord]:
    """Trace ``fn(*args, **kwargs)`` under ``eval_shape`` with
    ``pl.pallas_call`` replaced by a recording spy. Returns every launch
    the trace hit. ``fn`` is unwrapped past ``jax.jit`` so the trace
    always runs (a warm jit cache would skip the spy); ``kwargs`` are
    bound as Python values (``eval_shape`` would otherwise trace them)."""
    import functools

    records: List[LaunchRecord] = []
    raw = inspect.unwrap(fn)
    mod = raw.__module__
    if kwargs:
        raw = functools.partial(raw, **kwargs)
    path, line = _anchor(fn)

    def spy(kernel, *, grid=None, in_specs=None, out_specs=None,
            out_shape=None, scratch_shapes=(), grid_spec=None, **_kw):
        prefetch = 0
        if grid_spec is not None:  # PrefetchScalarGridSpec
            prefetch = grid_spec.num_scalar_prefetch
            grid, in_specs = grid_spec.grid, grid_spec.in_specs
            out_specs = grid_spec.out_specs
            scratch_shapes = grid_spec.scratch_shapes

        def launch(*operands):
            operands = operands[prefetch:]  # scalars live in SMEM
            in_blocks = []
            for spec, op in zip(_as_seq(in_specs), operands):
                bs = tuple(spec.block_shape)
                in_blocks.append((bs, tuple(op.shape),
                                  _block_bytes(bs, op.dtype)))
            outs = _as_seq(out_shape)
            out_blocks = []
            for spec, o in zip(_as_seq(out_specs), outs):
                bs = tuple(spec.block_shape)
                out_blocks.append((bs, tuple(o.shape),
                                   _block_bytes(bs, o.dtype)))
            scratch = 0
            for s in _as_seq(scratch_shapes):
                shape = tuple(getattr(s, "shape", ()) or ())
                dtype = getattr(s, "dtype", jnp.float32)
                scratch += _block_bytes(shape, dtype)
            kname = getattr(kernel, "__name__", None) or getattr(
                getattr(kernel, "func", None), "__name__", "<kernel>")
            records.append(LaunchRecord(
                kernel_name=f"{mod}.{kname}",
                path=path, line=line,
                grid=tuple(int(g) for g in _as_seq(grid)) or (1,),
                in_blocks=in_blocks, out_blocks=out_blocks,
                scratch_bytes=scratch))
            if isinstance(out_shape, (list, tuple)):
                return type(out_shape)(
                    jnp.zeros(o.shape, o.dtype) for o in out_shape)
            return jnp.zeros(out_shape.shape, out_shape.dtype)

        return launch

    orig = pl.pallas_call
    pl.pallas_call = spy
    try:
        jax.eval_shape(raw, *args)
    finally:
        pl.pallas_call = orig
    return records


def check_launch(rec: LaunchRecord, rel_path: str) -> List[Finding]:
    """Divisibility + VMEM findings for one captured launch."""
    out: List[Finding] = []
    for kind, blocks in (("in", rec.in_blocks), ("out", rec.out_blocks)):
        for i, (bs, shape, _) in enumerate(blocks):
            if len(bs) != len(shape):
                out.append(Finding(
                    "pallas-block-divisibility", rel_path, rec.line, 0,
                    f"{rec.kernel_name}: {kind}_specs[{i}] block rank "
                    f"{len(bs)} != operand rank {len(shape)}"))
                continue
            for b, d in zip(bs, shape):
                if b is None:
                    continue
                if b <= 0 or d % b != 0:
                    out.append(Finding(
                        "pallas-block-divisibility", rel_path, rec.line, 0,
                        f"{rec.kernel_name}: {kind}_specs[{i}] block dim "
                        f"{b} does not divide operand dim {d} "
                        f"(block {bs} vs shape {shape})"))
    if rec.vmem_bytes > VMEM_BUDGET_BYTES:
        out.append(Finding(
            "pallas-vmem-budget", rel_path, rec.line, 0,
            f"{rec.kernel_name}: analytic VMEM footprint "
            f"{rec.vmem_bytes / 2**20:.2f} MiB (blocks ×{DOUBLE_BUFFER} "
            f"double-buffer + scratch) exceeds the "
            f"{VMEM_BUDGET_BYTES // 2**20} MiB per-core budget"))
    return out


# ---------------------------------------------------------------------------
# representative launches for every kernel module in the repo
# ---------------------------------------------------------------------------


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def repo_launches() -> List[LaunchRecord]:
    """Capture every repo kernel at representative (small but dividing)
    shapes — the same geometry classes the serving engine launches."""
    from repro.kernels import (flash_attention, fused_nerf_mlp,
                               gather_trilerp, streaming_pipeline)

    recs: List[LaunchRecord] = []
    # GU gather: [num_mv=4, P=832, C=4] halo table, 8 ragged blocks of 64
    ragged = (_sds((4, 832, 4)), _sds((8,), jnp.int32), _sds((1,), jnp.int32),
              _sds((8, 8, 64), jnp.int32), _sds((8, 8, 64)))
    recs += record_launches(
        gather_trilerp.gather_trilerp_mvoxels_segmented, *ragged,
        interpret=True)
    # the tick's merged hole + reference sweep: the same ragged kernel
    recs += record_launches(streaming_pipeline.fused_gather_dual, *ragged,
                            interpret=True)
    # fused NeRF MLP: 1024 samples, width 64, direnc 27, block 512
    h, dd = 64, 27
    recs += record_launches(
        fused_nerf_mlp.fused_nerf_mlp,
        _sds((1024, 4)), _sds((1024, dd)), _sds((4, h)), _sds((1, h)),
        _sds((h, h)), _sds((1, h)), _sds((h, 1)), _sds((h + dd, 3)),
        _sds((1, 3)), block=512, interpret=True)
    # flash attention: GQA 4 q heads over 2 kv heads, 256 seq, d 64
    recs += record_launches(
        flash_attention.flash_attention,
        _sds((1, 4, 256, 64)), _sds((1, 2, 256, 64)), _sds((1, 2, 256, 64)),
        causal=True, block_q=128, block_k=128, interpret=True)
    return recs


def _rel(path: str, root) -> str:
    try:
        from pathlib import Path
        return Path(path).resolve().relative_to(Path(root).resolve()).as_posix()
    except ValueError:
        return path


# ---------------------------------------------------------------------------
# MVoxel layout bank-conflict recompute
# ---------------------------------------------------------------------------

REGISTERED_LAYOUTS = ("identity", "bank_interleaved")
_GATED_LAYOUTS = {"bank_interleaved": 1.0}  # must be conflict-free
_KNOWN_FACTORS = {"identity": 3.0}  # recorded, not gated


def recompute_bank_conflict(layout: str) -> Dict[str, Any]:
    """Independent bank-conflict recompute from the layout's row
    permutation (does NOT call ``streaming.bank_conflict_factor``).

    A trilerp reads the 8 corner rows of one voxel; rows interleave
    across ``num_banks`` SRAM banks as ``row % num_banks``. The factor is
    the mean (over every voxel base in the halo block) of the worst
    bank's serialized reads — 1.0 means all 8 corners hit distinct banks.
    """
    from repro.core import streaming

    cfg = streaming.StreamingCfg(layout=layout)
    p, e, b = cfg.mvoxel_edge + 1, cfg.mvoxel_edge, cfg.num_banks
    if layout == "identity":
        row_of = np.arange(p**3, dtype=np.int64)
        padded = p**3
        perm_ok = True
    else:
        rows, padded = streaming.layout_row_map(cfg)
        row_of = rows.astype(np.int64)
        # the map must be a true permutation into [0, padded): every halo
        # point keeps exactly one row, or apply_layout drops features
        perm_ok = (len(np.unique(row_of)) == p**3
                   and row_of.min() >= 0 and row_of.max() < padded)
    # x-major corner ids of every voxel base — recomputed here, not taken
    # from streaming/grids, so a convention drift there is caught
    ax = np.arange(e)
    bx, by, bz = np.meshgrid(ax, ax, ax, indexing="ij")
    base = np.stack([bx, by, bz], -1).reshape(-1, 3)
    offs = np.stack(np.meshgrid([0, 1], [0, 1], [0, 1],
                                indexing="ij"), -1).reshape(-1, 3)
    corners = base[:, None, :] + offs[None, :, :]
    ids = (corners[..., 0] * p + corners[..., 1]) * p + corners[..., 2]
    banks = row_of[ids] % b  # [voxels, 8]
    worst = np.stack([np.bincount(row, minlength=b).max() for row in banks])
    return {"layout": layout, "factor": float(worst.mean()),
            "rows": int(padded), "permutation_ok": bool(perm_ok)}


def check_layouts() -> Tuple[List[Finding], List[Dict[str, Any]]]:
    anchor_path = "src/repro/core/streaming.py"
    from repro.core import streaming
    line = inspect.getsourcelines(streaming.layout_row_map)[1]
    findings: List[Finding] = []
    stats: List[Dict[str, Any]] = []
    for layout in REGISTERED_LAYOUTS:
        st = recompute_bank_conflict(layout)
        stats.append(st)
        if not st["permutation_ok"]:
            findings.append(Finding(
                "mvoxel-bank-conflict", anchor_path, line, 0,
                f"layout '{layout}' row map is not a permutation — "
                "apply_layout would drop or duplicate halo rows"))
        gate = _GATED_LAYOUTS.get(layout)
        if gate is not None and st["factor"] != gate:
            findings.append(Finding(
                "mvoxel-bank-conflict", anchor_path, line, 0,
                f"layout '{layout}' bank-conflict factor "
                f"{st['factor']:.3f} != required {gate:.1f} — the 8 "
                "corners of a voxel no longer hit 8 distinct banks"))
    return findings, stats


def run(root) -> Tuple[List[Finding], Dict[str, Any]]:
    """Full Pallas pass: (findings, stats-for-the-bench-block)."""
    findings: List[Finding] = []
    kernels = []
    for rec in repo_launches():
        rel = _rel(rec.path, root)
        findings.extend(check_launch(rec, rel))
        kernels.append({
            "kernel": rec.kernel_name, "grid": list(rec.grid),
            "vmem_bytes": rec.vmem_bytes,
        })
    layout_findings, layout_stats = check_layouts()
    findings.extend(layout_findings)
    return findings, {"kernels": kernels, "layouts": layout_stats}
