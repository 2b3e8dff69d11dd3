"""Block/grid-size autotuner for the streaming Pallas kernels.

The segmented gather and the fused streaming pipeline both tile their work
as ``[num_seg * num_mv, ..., cap]`` RIT blocks: ``cap`` (samples per
(segment, MVoxel) block) fixes the Pallas block shape; the fused kernel
gives its reference set twice the hole capacity, as the serving tick
does. The best block size is hardware-dependent (MXU tile
amortization vs VMEM footprint vs padding waste), so instead of hardcoding
it we sweep a pow2 ladder, time each candidate on synthetic RIT blocks at
the config's true streaming shapes, and cache the winner keyed on
``RenderConfig.fingerprint()`` — the digest of the exact compile surface,
so a cache hit is only ever served to the configuration it was measured
on.

  PYTHONPATH=src python benchmarks/autotune.py           # standing config
  PYTHONPATH=src python benchmarks/autotune.py --smoke   # tiny sweep
  PYTHONPATH=src python benchmarks/autotune.py --force   # re-measure

The cache (``benchmarks/.autotune_cache.json`` by default, gitignored)
maps fingerprint → winning block config + measured wall-clocks. It is a
standalone report: no engine reads it, so what the program compiles
depends only on committed files and the ``RenderConfig`` it is given.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

DEFAULT_CACHE = Path(__file__).resolve().parent / ".autotune_cache.json"


def _load_cache(path: Path) -> Dict[str, dict]:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return {}


def _time_best(fn, reps: int = 3) -> float:
    """Best-of-N steady-state wall clock (first call compiles, untimed)."""
    import jax

    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        jax.block_until_ready(fn())
        best = min(best, time.time() - t0)
    return best


def _synthetic_blocks(key, num_seg: int, num_mv: int, cap: int, p: int,
                      channels: int):
    """Synthetic RIT blocks at the kernel's true shapes: uniform random
    local ids + unit-sum weights (the kernel's cost is id-independent —
    one-hot matmuls — so uniform ids time the real schedule)."""
    import jax
    import jax.numpy as jnp

    k1, k2 = jax.random.split(key)
    ids = jax.random.randint(k1, (num_seg * num_mv, 8, cap), 0, p,
                             dtype=jnp.int32)
    w = jax.random.uniform(k2, (num_seg * num_mv, 8, cap), jnp.float32)
    w = w / jnp.sum(w, axis=1, keepdims=True)
    return ids, w


def _cap_ladder(base_cap: int, smoke: bool) -> List[int]:
    caps = [base_cap // 4, base_cap // 2, base_cap]
    if not smoke:
        caps.append(base_cap * 2)
    return sorted({max(c, 32) for c in caps})


def autotune(cfg, *, cache_path: Path = DEFAULT_CACHE, force: bool = False,
             smoke: bool = False, num_seg: Optional[int] = None) -> dict:
    """Sweep RIT block sizes for ``cfg`` and cache the winner.

    ``cfg`` is a (resolved) :class:`repro.core.config.RenderConfig`; the
    sweep runs at its true streaming shapes (grid_res / MVoxel edge /
    channels, ``num_seg`` sessions — default ``cfg.num_slots``). Returns
    the cache entry: per-kernel candidate timings plus the winning
    ``capacity`` of each kernel.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import streaming
    from repro.kernels import gather_trilerp, streaming_pipeline

    key = cfg.fingerprint()
    cache = _load_cache(cache_path)
    if key in cache and not force:
        return cache[key]

    s = int(num_seg) if num_seg is not None else int(cfg.num_slots)
    scfg = streaming.StreamingCfg(grid_res=cfg.grid_res,
                                  mvoxel_edge=8,
                                  capacity=cfg.stream_capacity,
                                  layout=cfg.mvoxel_layout)
    num_mv, p, c = scfg.num_mvoxels, scfg.halo_rows, cfg.channels
    interpret = cfg.resolved_pallas_interpret()
    rng = jax.random.PRNGKey(0)
    mv_table = jax.random.normal(rng, (num_mv, p, c), jnp.float32)

    # --- segmented gather: sweep the per-block RIT capacity --------------
    seg_rows = []
    for cap in _cap_ladder(cfg.stream_capacity, smoke):
        ids, w = _synthetic_blocks(rng, s, num_mv, cap, p, c)
        wall = _time_best(lambda: gather_trilerp.gather_trilerp_mvoxels_segmented(
            mv_table, ids, w, num_seg=s, interpret=interpret))
        # normalize to per-sample-slot cost: bigger blocks do more work
        # per call, the tuner optimizes throughput, not latency
        seg_rows.append({"capacity": cap, "wall_s": wall,
                         "ns_per_slot": wall * 1e9 / (s * num_mv * cap)})
    seg_best = min(seg_rows, key=lambda r: r["ns_per_slot"])

    # --- fused pipeline: sweep the hole capacity (reference at 2x) --------
    fused_rows = []
    for cap in _cap_ladder(cfg.stream_capacity, smoke):
        ids_h, w_h = _synthetic_blocks(rng, s, num_mv, cap, p, c)
        ids_r, w_r = _synthetic_blocks(rng, s, num_mv, cap * 2, p, c)
        wall = _time_best(lambda: streaming_pipeline.fused_gather_dual(
            mv_table, ids_h, w_h, ids_r, w_r, num_seg=s,
            interpret=interpret))
        slots = s * num_mv * cap * 3
        fused_rows.append({"capacity": cap, "wall_s": wall,
                           "ns_per_slot": wall * 1e9 / slots})
    fused_best = min(fused_rows, key=lambda r: r["ns_per_slot"])

    entry = {
        "config_fingerprint": key,
        "num_seg": s,
        "num_mvoxels": num_mv,
        "halo_rows": p,
        "channels": c,
        "pallas_interpret": interpret,
        "segmented_gather": {"best": seg_best, "candidates": seg_rows},
        "fused_pipeline": {"best": fused_best, "candidates": fused_rows},
    }
    cache[key] = entry
    cache_path.parent.mkdir(parents=True, exist_ok=True)
    cache_path.write_text(json.dumps(cache, indent=2) + "\n")
    return entry


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep (small grid, fewer candidates)")
    ap.add_argument("--force", action="store_true",
                    help="re-measure even on a cache hit")
    ap.add_argument("--cache", default=str(DEFAULT_CACHE))
    ap.add_argument("--sessions", type=int, default=None)
    args = ap.parse_args()

    from repro.core.config import RenderConfig

    if args.smoke:
        cfg = RenderConfig(res=32, grid_res=16, channels=4,
                           decoder="direct", num_samples=16,
                           backend="streaming", stream_capacity=128,
                           num_slots=2).resolved()
    else:
        # the standing 4-session serving geometry (benchmarks/run.py)
        cfg = RenderConfig(res=64, grid_res=48, channels=4,
                           decoder="direct", num_samples=32,
                           backend="streaming", num_slots=4).resolved()
    entry = autotune(cfg, cache_path=Path(args.cache), force=args.force,
                     smoke=args.smoke, num_seg=args.sessions)
    print(json.dumps(entry, indent=2))


if __name__ == "__main__":
    main()
