"""Open-loop load harness for the multi-scene render-serving engine.

Generates a synthetic serving workload the way serving papers do —
arrivals are **open-loop** (a Poisson process that does not wait for the
engine; backlog is allowed to build), scene popularity is **Zipf** (a few
hot scenes dominate, a long tail keeps the cache honest), trajectory
lengths are **heavy-tailed** (Pareto: most sessions are short, a few run
long and pin their scene's page), and session churn is continuous (slots
drain and re-admit throughout) — then drives
:class:`~repro.serve.render_engine.RenderServeEngine` through two phases:

* **uncontended** — arrival rate below service capacity. Measures the
  baseline frame-latency distribution, the scene-cache hit rate under
  Zipf popularity (gate: >= 0.7 with 8 scenes paged through a 4-slot
  engine), the steady mixed-scene sweep count (gate: <= 2 sweeps/tick),
  and — via :class:`~repro.analysis.jitprobe.JitCacheProbe` — that scene
  churn compiles NOTHING after warmup.
* **overload** — a burst far beyond capacity with per-session deadlines
  under the ``priority`` policy. The deadline policy must SHED the
  unservable tail (gate: shed > 0) so the admitted sessions' p95 frame
  latency stays bounded (gate: <= 3x the uncontended p95) instead of
  every session queueing toward collapse.

Arrivals are clocked in **ticks** (the engine's natural service quantum)
so the workload is reproducible across machines; deadlines and latencies
are wall-clock, with the overload deadline set from the measured
uncontended tick time so the shedding behavior is machine-independent.

  PYTHONPATH=src python benchmarks/load.py            # full harness
  PYTHONPATH=src python benchmarks/load.py --smoke    # <120 s CI arm
                                                      # (2 scenes + burst)

``benchmarks/run.py --sessions N`` embeds the result as the gated
``load`` block of ``BENCH_render.json``.
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


# ---------------------------------------------------------------------------
# workload generation
# ---------------------------------------------------------------------------


def make_workload(num_sessions: int, scene_pool: List[str], window: int, *,
                  zipf_exponent: float = 1.4, arrivals_per_tick: float = 1.0,
                  max_windows: int = 6, tail_alpha: float = 1.5,
                  burst: bool = False, seed: int = 0) -> List[Dict]:
    """Synthesize ``num_sessions`` session specs.

    Returns dicts of ``arrive_tick`` (Poisson process in tick time, or 0
    for a burst), ``scene`` (Zipf-ranked over ``scene_pool``), ``frames``
    (heavy-tailed: ``window * (1 + Pareto(tail_alpha))``, clipped to
    ``max_windows`` so one straggler can't own the harness), and
    ``phase_deg`` (each client orbits from its own start)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    if burst:
        arrive = np.zeros(num_sessions, dtype=int)
    else:
        gaps = rng.exponential(1.0 / arrivals_per_tick, size=num_sessions)
        arrive = np.floor(np.cumsum(gaps)).astype(int)
    ranks = np.arange(1, len(scene_pool) + 1, dtype=float)
    popularity = ranks ** -zipf_exponent
    popularity /= popularity.sum()
    scene_ix = rng.choice(len(scene_pool), size=num_sessions, p=popularity)
    windows = 1 + np.floor(rng.pareto(tail_alpha, size=num_sessions))
    windows = np.clip(windows.astype(int), 1, max_windows)
    phases = rng.uniform(0.0, 360.0, size=num_sessions)
    return [dict(arrive_tick=int(arrive[i]),
                 scene=scene_pool[int(scene_ix[i])],
                 frames=int(windows[i] * window),
                 phase_deg=float(phases[i]))
            for i in range(num_sessions)]


# ---------------------------------------------------------------------------
# open-loop driver
# ---------------------------------------------------------------------------


def drive_open_loop(engine, specs: List[Dict], *, sid_base: int = 0,
                    deadline_ms: Optional[float] = None,
                    max_ticks: int = 10_000) -> Dict:
    """Drive the engine tick by tick, injecting arrivals from ``specs`` as
    their tick-clock comes due (never waiting for completions — the
    open-loop contract: backlog builds when the engine falls behind).

    Returns the phase's measurements: end-to-end frame latencies (from
    arrival or the previous delivery to delivery), queue-wait
    distribution, shed count, tick wall-clocks, and per-run scene-cache /
    sweep deltas (the engine's lifetime counters snapshotted here, the
    ``pool.recompiles`` convention)."""
    import numpy as np

    from repro.core import pipeline
    from repro.kernels import streaming_pipeline
    from repro.serve.render_engine import RenderSession, delivery_latencies

    specs = sorted(specs, key=lambda d: d["arrive_tick"])
    sessions: List[RenderSession] = []
    start_ticks = engine.num_ticks
    adm_start = engine._num_admission_ticks
    shed_start = engine._num_shed
    sc_start = (dict(engine.scene_cache.counters(),
                     uploads=engine._num_uploads)
                if engine.multi_scene else None)
    tick_walls: List[float] = []
    i, tick = 0, 0
    t0 = time.time()
    while tick < max_ticks:
        while i < len(specs) and specs[i]["arrive_tick"] <= tick:
            s = specs[i]
            sess = RenderSession(
                sid=sid_base + i, scene=s["scene"], deadline_ms=deadline_ms,
                poses=list(pipeline.orbit_trajectory(
                    s["frames"], step_deg=4.0, phase_deg=s["phase_deg"])))
            engine.submit([sess])
            sessions.append(sess)
            i += 1
        tick_t0 = time.time()
        if not engine.step():
            if i < len(specs):
                tick += 1  # idle gap in the arrival process
                continue
            break
        # closed per tick: block and deliver the frames (the harness
        # measures latency, so it forgoes run()'s 1-tick pipelining)
        engine.finalize()
        tick_walls.append(time.time() - tick_t0)
        tick += 1
    wall_s = time.time() - t0

    served = [s for s in sessions if not s.shed]
    waits = [s.admitted_s - s.submitted_s for s in served
             if s.admitted_s is not None]
    # end-to-end frame latency: from the session's previous delivery (its
    # arrival, for the first window, so the queue wait is in it) to the
    # frame's delivery
    e2e = [lat for s in served for lat in delivery_latencies(s)]
    frames_done = len(e2e)
    ticks_run = engine.num_ticks - start_ticks
    adm_ticks = engine._num_admission_ticks - adm_start

    out = dict(
        sessions=len(sessions),
        served=len(served),
        shed=engine._num_shed - shed_start,
        ticks=ticks_run,
        frames=frames_done,
        wall_s=wall_s,
        aggregate_fps=frames_done / max(wall_s, 1e-9),
        tick_p50_s=float(np.percentile(tick_walls, 50)) if tick_walls else 0.0,
        frame_p50_s=float(np.percentile(e2e, 50)) if e2e else float("nan"),
        frame_p95_s=float(np.percentile(e2e, 95)) if e2e else float("nan"),
        queue_wait_p50_s=float(np.percentile(waits, 50)) if waits else 0.0,
        queue_wait_p95_s=float(np.percentile(waits, 95)) if waits else 0.0,
    )
    if engine.multi_scene:
        end = dict(engine.scene_cache.counters(), uploads=engine._num_uploads)
        cache = {k: end[k] - sc_start[k]
                 for k in ("hits", "misses", "evictions", "uploads")}
        cache["hit_rate"] = cache["hits"] / max(
            cache["hits"] + cache["misses"], 1)
        cache["resident_scenes"] = end["entries"]
        out["scene_cache"] = cache
    if engine.engine._seg_aware and ticks_run:
        mem = engine.engine.tick_memory_stats(engine.num_slots, engine.window)
        steady = 1.0 if engine.fused else mem["staged_table_sweeps_per_tick"]
        out["sweeps_per_tick_steady"] = steady
        out["sweeps_per_tick_amortized"] = (
            streaming_pipeline.serving_sweeps_per_tick(
                ticks_run, adm_ticks, mem["staged_ref_sweeps"])
            if engine.fused else steady)
    return out


# ---------------------------------------------------------------------------
# the benchmark: uncontended phase + overload burst, gated
# ---------------------------------------------------------------------------


def bench_load(smoke: bool = False, seed: int = 0) -> Dict:
    """Two-phase open-loop load measurement; returns the gated ``load``
    block for ``BENCH_render.json``. Smoke (< 120 s): 2 scenes over a
    2-slot engine plus the overload burst — the mechanism checks (shed
    active, bounded p95, zero churn recompiles) without the Zipf-scale
    cache statistics."""
    from repro import api
    from repro.analysis.jitprobe import JitCacheProbe
    from repro.core import pipeline
    from repro.core.config import RenderConfig
    from repro.nerf import scenes
    from repro.serve.render_engine import RenderServeEngine, RenderSession

    if smoke:
        num_slots, window, res = 2, 2, 24
        scene_pool = scenes.SCENE_NAMES[:2]
        n_open, n_burst = 8, 6
    else:
        num_slots, window, res = 4, 2, 32
        scene_pool = list(scenes.SCENE_NAMES)  # 8 scenes over 4 pages
        n_open, n_burst = 40, 16
    # pool_bucket pinned: the hole-cap ladder would otherwise recompile
    # mid-run and the churn-recompile gate could not distinguish ladder
    # steps from scene-churn retraces (the thing this harness polices)
    cfg = RenderConfig(scene=scene_pool[0], res=res, window=window,
                       grid_res=16, channels=4, decoder="direct",
                       num_samples=8, backend="streaming", num_slots=num_slots,
                       pool_holes=True, pool_bucket=256,
                       fused_tick=True).resolved()
    r = api.make_renderer(cfg)

    def loader(name):
        return scenes.bake_dense_table(scenes.make_scene(name),
                                       r.model.cfg.grid_res,
                                       r.model.cfg.channels)

    engine = RenderServeEngine(r.model, r.params, config=cfg,
                               scene_loader=loader, policy="priority")

    # --- warmup: compile tick + prime, page two scenes ------------------
    engine.run([RenderSession(sid=10_000 + i, scene=scene_pool[i % 2],
                              poses=list(pipeline.orbit_trajectory(window)))
                for i in range(2)])

    probe = JitCacheProbe(engine.engine)

    # --- phase 1: uncontended open-loop (Zipf scenes, heavy-tail lengths)
    open_specs = make_workload(
        n_open, scene_pool, window, zipf_exponent=1.4,
        arrivals_per_tick=0.5 * num_slots, burst=False, seed=seed)
    uncontended = drive_open_loop(engine, open_specs, sid_base=0)

    # --- phase 2: overload burst with deadlines (priority policy sheds) --
    # deadline = one measured tick: queued sessions that cannot start
    # within a tick of service are past useful latency — shed them
    deadline_ms = max(uncontended["tick_p50_s"] * 1e3, 10.0)
    burst_specs = make_workload(
        n_burst, scene_pool, window, zipf_exponent=1.4, burst=True,
        seed=seed + 1)
    overload = drive_open_loop(engine, burst_specs, sid_base=1000,
                               deadline_ms=deadline_ms)
    overload["deadline_ms"] = deadline_ms

    churn_recompiles = probe.recompiles()

    p95_ratio = overload["frame_p95_s"] / max(uncontended["frame_p95_s"],
                                              1e-9)
    hit_rate = uncontended["scene_cache"]["hit_rate"]
    steady = uncontended.get("sweeps_per_tick_steady", float("nan"))
    gates = {
        # Zipf over >= 8 scenes through num_slots pages must keep the hot
        # set resident (full harness; smoke's 2-scene pool is trivially hot)
        "hit_rate_min": 0.7,
        "hit_rate_met": hit_rate >= 0.7,
        "max_steady_sweeps_per_tick": 2.0,
        "steady_sweeps_met": steady <= 2.0,
        # overload must shed, and the ADMITTED sessions' tail latency must
        # stay bounded (vs collapsing as the backlog queues toward infinity)
        "shed_active": overload["shed"] > 0,
        "overload_p95_ratio": p95_ratio,
        "overload_p95_max_ratio": 3.0,
        "overload_p95_met": p95_ratio <= 3.0,
        # scene churn re-steers traced inputs, it never retraces
        "recompiles_after_warmup": churn_recompiles,
        "recompile_gate_met": churn_recompiles == 0,
    }
    gates["all_met"] = all(v for k, v in gates.items()
                           if k.endswith("_met") or k == "shed_active")
    return {
        "smoke": smoke,
        "scenes": len(scene_pool),
        "num_slots": num_slots,
        "window": window,
        "res": res,
        "zipf_exponent": 1.4,
        "policy": "priority",
        "config_fingerprint": cfg.fingerprint(),
        "uncontended": uncontended,
        "overload": overload,
        "scene_cache_hit_rate": hit_rate,
        "gates": gates,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="<120 s arm: 2 scenes, overload burst, all gates")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="write the load block to this JSON file")
    args = ap.parse_args()
    from repro.utils import enable_compilation_cache

    enable_compilation_cache(ROOT)
    block = bench_load(smoke=args.smoke, seed=args.seed)
    print(json.dumps(block, indent=2))
    if args.out:
        Path(args.out).write_text(json.dumps(block, indent=2) + "\n")
    if not block["gates"]["all_met"]:
        print("FAIL: load gates not met: " + json.dumps(block["gates"]))
        sys.exit(1)


if __name__ == "__main__":
    main()
