"""Block-size autotuner for the streaming Pallas kernel.

Every gather path (the fused tick's ``fused_gather_dual``, the staged
path's and the prime's ``gather_trilerp_mvoxels_segmented``) runs one
ragged-RIT kernel over ``[n_blocks, ..., cap]`` blocks: ``cap`` (samples
per RIT block) fixes the Pallas block shape, and an MVoxel's run pads up
to a whole block. The best block size is hardware-dependent (MXU tile
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


def _synthetic_blocks(key, samples: int, num_mv: int, cap: int, p: int):
    """A synthetic ragged RIT at the kernel's true shapes: ``samples``
    samples spread uniformly over ``num_mv`` MVoxels, cut into blocks of
    ``cap``, with uniform random local ids and unit-sum weights (the
    kernel's cost is id-independent — one-hot matmuls — so uniform ids
    time the real schedule)."""
    import jax
    import jax.numpy as jnp

    from repro.core import streaming

    k1, k2, k3 = jax.random.split(key, 3)
    rit = streaming.build_rit(
        jax.random.randint(k1, (samples,), 0, num_mv, dtype=jnp.int32),
        num_mv, cap)
    shape = (rit.sample.shape[0], 8, cap)
    ids = jax.random.randint(k2, shape, 0, p, dtype=jnp.int32)
    w = jax.random.uniform(k3, shape, jnp.float32)
    return rit, ids, w / jnp.sum(w, axis=1, keepdims=True)


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
    the cache entry: candidate timings plus the winning ``capacity``.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import streaming
    from repro.kernels import gather_trilerp

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

    # --- ragged gather: sweep the RIT block width over one tick's samples
    # (every session's frame of reference samples and a quarter frame of
    # hole samples)
    samples = s * cfg.res * cfg.res * cfg.num_samples * 5 // 4
    rows = []
    for cap in _cap_ladder(cfg.stream_capacity, smoke):
        rit, ids, w = _synthetic_blocks(rng, samples, num_mv, cap, p)
        wall = _time_best(lambda: gather_trilerp.gather_trilerp_mvoxels_segmented(
            mv_table, rit.block_key, rit.n_live, ids, w,
            interpret=interpret))
        # normalize to per-sample cost: the tuner optimizes throughput,
        # padding included
        rows.append({"capacity": cap, "wall_s": wall,
                     "live_blocks": int(rit.n_live[0]),
                     "ns_per_sample": wall * 1e9 / samples})
    best = min(rows, key=lambda r: r["ns_per_sample"])

    entry = {
        "config_fingerprint": key,
        "num_seg": s,
        "num_mvoxels": num_mv,
        "halo_rows": p,
        "channels": c,
        "pallas_interpret": interpret,
        "samples": samples,
        "ragged_gather": {"best": best, "candidates": rows},
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
