"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``). The configuration names its architecture
(``"arch"``), whose module ``bench/arch/<arch>.py`` makes the weights,
builds the program's model, and gives the reference its field and the
work counts their per-sample terms. One process serves one run:

1. It refuses a backend other than the TPU, Pallas in interpret mode, and
   fewer chips than the cell asks for: it exits non-zero and prints no
   result.
2. Set-up: the weights are made on the device from the seed (the
   architecture's ``make_weights``), the serving engine is built as
   ``repro.api.make_renderer(cfg).pipeline.serve_engine_for(cfg)`` builds
   it, and the first viewers' sessions are admitted and served one tick,
   which primes the engine and warms every program and shape the window
   runs (from the compilation cache in ``.jax_cache/`` after the first run
   in a checkout).
3. The window opens at a dispatch. The loop is a copy of
   ``RenderServeEngine.run()``'s one-tick-ahead loop over the public
   ``step()`` and ``finalize(keep=1)``, stamping when each tick's frames
   reach the host. It dispatches ticks for ``--seconds`` seconds and ends
   when the last of them is delivered. Viewers form a closed loop: a
   viewer's next session arrives when its previous one's last frame does.
4. Once the window has closed and the engine is freed, a sample of the
   delivered session windows, drawn from the seed, is rendered again by
   the plain reference (``reference.py``) and compared
   (``correct``), each compared number beside its limit
   (``bench/limits/<cell>.json``).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries the
cell's per-layer metrics, each computed by its reader
``bench/metrics/<metric>.py`` from the run record and the trace.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

import traffic  # noqa: E402


class NoChip(RuntimeError):
    """No accelerator the cell can run on."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what BENCHMARK.json and the data files say about a cell
# ---------------------------------------------------------------------------


def load_cell(name: str, benchmark: Optional[dict] = None,
              root: Path = ROOT) -> dict:
    """The cell ``name``: its workload entry, configuration, traffic mix,
    limits and the metric entries it reports. ``benchmark`` is
    ``BENCHMARK.json``'s content, read from ``root`` when not given; the
    data files it names (configurations, and each cell's mix and limits
    under ``bench/``) are read under ``root``. The configuration's
    architecture module is loaded, so that a configuration without one
    fails here."""
    if benchmark is None:
        benchmark = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in benchmark["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    load_arch(config)
    data = root / BENCH.name

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "name": name,
        "chips": cell["chips"],
        "config": config,
        "mix": traffic.load(cell["traffic"], data / "traffic"),
        "limits": json.loads((data / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in benchmark["end_to_end"] if applies(m)],
        "per_layer": [m for m in benchmark["per_layer"] if applies(m)],
    }


def load_arch(cfg: dict):
    """The module ``bench/arch/<arch>.py`` of the architecture that the
    configuration ``cfg`` names (``"arch"``). Loaded once per process, so
    that the reference's compiled programs, keyed on its ``field``, are
    reused."""
    name = cfg.get("arch")
    if not isinstance(name, str) \
            or not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise ValueError(f"configuration {cfg.get('name')!r} names no "
                         f"architecture (\"arch\"): {name!r}")
    key = f"bench_arch_{name}"
    if key in sys.modules:
        return sys.modules[key]
    path = BENCH / "arch" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {cfg.get('name')!r} names "
                                f"the architecture {name!r}, which has no "
                                f"module {path}")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[key] = mod
    return mod


def metric_reader(name: str) -> Callable:
    """``read(run, trace)`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# device checks
# ---------------------------------------------------------------------------


def check_device(chips: int) -> None:
    import jax

    if jax.default_backend() != "tpu":
        raise NoChip(f"no TPU: JAX's backend is {jax.default_backend()!r}")
    # imported only now: importing the program compiles its constants
    from repro.kernels.common import resolve_interpret

    if resolve_interpret(None):
        raise NoChip("Pallas resolved to interpret mode")
    if len(jax.devices()) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(jax.devices())}")


def enable_cache(jax) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache's key), holding every program,
    however quick to compile, so that only a checkout's first run
    compiles."""
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no eviction: an evicting cache needs an access-time file beside every
    # entry, and one entry without it stops every later write
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_info() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


class CompileClock:
    """Counts the backend compiles JAX reports, and their seconds."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def render_config(cfg: dict):
    from repro.core.config import RenderConfig

    return RenderConfig(
        scene=cfg["scene"], res=cfg["res"], window=cfg["window"],
        num_slots=cfg["num_slots"], backend="streaming", fused_tick=True,
        pool_holes=True, pool_bucket=cfg["pool_bucket"],
        ray_chunk=cfg["ray_chunk"],
        **load_arch(cfg).config_fields(cfg)).resolved()


def build_engine(cfg: dict, weights: dict):
    """The serving engine exactly as ``repro.api`` builds it, over the
    benchmark's weights."""
    from repro import api

    rcfg = render_config(cfg)
    if rcfg.camera.focal <= 0 or cfg["fov_deg"] != 50.0:
        raise ValueError("the serving camera is the repository's 50-degree "
                         "square camera")
    model, params = load_arch(cfg).build_model(cfg, rcfg, weights)
    renderer = api.make_renderer(rcfg, model=model, params=params)
    return renderer.pipeline.serve_engine_for(rcfg)


class Viewers:
    """The closed loop: each viewer's sessions in order, the session it
    watches now, and when its last window reached it."""

    def __init__(self, mix: dict, window: int, seed: int):
        self.mix = mix
        self.queue = traffic.sessions(mix, window, seed)
        self.next_sid = 0
        self.last_delivery: Dict[int, float] = {}
        self.poses: Dict[int, np.ndarray] = {}   # sid -> [frames, 4, 4]
        self.viewer_of: Dict[int, int] = {}

    def arrive(self, viewer: int, engine, now: float):
        """Submit the viewer's next session to the engine."""
        from repro.serve.render_engine import RenderSession

        spec = self.queue.pop(0)
        sid = self.next_sid
        self.next_sid += 1
        poses = traffic.orbit_poses(spec, self.mix["motion"])
        self.poses[sid] = poses
        self.viewer_of[sid] = viewer
        sess = RenderSession(sid=sid, poses=list(poses))
        sess.submitted_s = now
        engine.submit([sess])
        self.last_delivery[viewer] = now
        return sess


def serve_window(engine, viewers: Viewers, cfg: dict, seconds: float,
                 trace_dir: Optional[Path], clock: CompileClock,
                 fault: Optional[Callable] = None) -> dict:
    """Set-up's first tick, then the measured window. Returns the run
    record: per delivered session window its frames, stamps and counts."""
    import jax

    window = cfg["window"]
    hw = cfg["res"] ** 2
    slots = cfg["num_slots"]

    def dispatch() -> dict:
        occupied_before = [s.session if s is not None else None
                           for s in engine.slots]
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
        assignments, result = engine._pending[-1][0], engine._last_result
        if fault is not None:
            result = fault(engine, result)
            engine._last_result = result
            engine._pending[-1] = (assignments, result,
                                   engine._pending[-1][2])
        admitted = [s for s in range(slots)
                    if assignments[s] is not None
                    and assignments[s][0] is not occupied_before[s]]
        next_live = [s for s in range(slots)
                     if engine.slots[s] is not None
                     and assignments[s] is not None
                     and engine.slots[s].session is assignments[s][0]]
        return {"assignments": [(a[0], list(a[1])) if a is not None else None
                                for a in assignments],
                "result": result, "admitted": admitted,
                "next_live": next_live, "dispatched_s": time.time()}

    def deliver(tick: dict, records: List[dict], in_window: bool) -> None:
        res = tick["result"]
        with jax.profiler.TraceAnnotation("bench.deliver"):
            frames = np.asarray(res.frames)
        now = time.time()
        with jax.profiler.TraceAnnotation("bench.finalize"):
            engine.finalize(keep=1 if engine._pending
                            and engine._pending[-1][1] is not res else 0)
        counts = np.asarray(res.hole_counts)
        rit = np.asarray(res.rit_counts)
        tick.update(delivered_s=now, rit=rit, hole_total=0, frames_live=0)
        for s, a in enumerate(tick["assignments"]):
            if a is None:
                continue
            sess, idxs = a
            viewer = viewers.viewer_of[sess.sid]
            first = idxs[0] == 0
            start = (sess.submitted_s if first
                     else viewers.last_delivery[viewer])
            records.append({
                "tick": tick["index"], "slot": s, "sid": sess.sid,
                "viewer": viewer, "start": idxs[0], "count": len(idxs),
                "frames": frames[s, :len(idxs)].reshape(len(idxs), hw, 3),
                "hole_counts": counts[s, :len(idxs)].tolist(),
                "delivered_s": now, "latency_s": now - start,
                "first": first, "submitted_s": sess.submitted_s,
                "admitted_s": sess.admitted_s, "in_window": in_window})
            tick["hole_total"] += int(counts[s, :len(idxs)].sum())
            tick["frames_live"] += len(idxs)
            viewers.last_delivery[viewer] = now
            if idxs[-1] == len(sess.poses) - 1:
                with jax.profiler.TraceAnnotation("bench.arrive"):
                    viewers.arrive(viewer, engine, now)
        # keep the co-rendered next references for the correctness check
        tick["next_ref"] = (res.next_rgb_ref, res.next_dep_ref)
        tick["result"] = None

    records: List[dict] = []
    ticks: List[dict] = []
    # set-up: the first viewers (one per slot) arrive and are served one
    # tick, which admits and primes them and warms every program
    for v in range(min(slots, viewers.mix["viewers"])):
        viewers.arrive(v, engine, time.time())
    tick = dispatch()
    tick["index"] = -1
    deliver(tick, records, in_window=False)
    warm_ticks = [tick]
    t_setup_end = time.time()
    # the remaining viewers arrive as the window opens
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    t_open = time.time()
    compiles_open = clock.count
    for v in range(slots, viewers.mix["viewers"]):
        viewers.arrive(v, engine, t_open)
    in_flight: List[dict] = []
    while time.time() - t_open < seconds:
        tick = dispatch()
        tick["index"] = len(ticks)
        ticks.append(tick)
        in_flight.append(tick)
        if len(in_flight) > 1:
            deliver(in_flight.pop(0), records, in_window=True)
    for tick in in_flight:
        deliver(tick, records, in_window=True)
    t_end = time.time()
    compiles_window = clock.count - compiles_open
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return {"records": records, "ticks": ticks, "warm_ticks": warm_ticks,
            "t_setup_end": t_setup_end, "t_open": t_open, "t_end": t_end,
            "window": window, "hw": hw, "compiles_window": compiles_window}


# ---------------------------------------------------------------------------
# correctness: the plain reference over a seeded sample of windows
# ---------------------------------------------------------------------------


def pick_windows(records: List[dict], count: int, seed: int) -> List[dict]:
    """A seeded sample of ``count`` of the window's delivered session
    windows: up to half of them sessions' first windows (slot reuse and
    the admission prime), the rest later windows (the co-rendered
    reference), each part drawn at random and topped up from the other."""
    pool = [r for r in records if r["in_window"]]
    first = [r for r in pool if r["first"]]
    rest = [r for r in pool if not r["first"]]
    rng = np.random.default_rng(seed)
    n_first = min(len(first), max(count - len(rest), -(-count // 2)))
    n_rest = min(len(rest), count - n_first)

    def draw(group, n):
        return [group[i] for i in rng.choice(len(group), n, replace=False)]

    picked = draw(first, n_first) + draw(rest, n_rest)
    return sorted(picked, key=lambda r: (r["tick"], r["slot"]))


def next_refs(run: dict) -> Dict[tuple, tuple]:
    """``(sid, window start)`` -> the reference frame (colour, depth) the
    program co-rendered for that window, for every window whose previous
    window was served by a tick of this run."""
    out = {}
    for tick in run["warm_ticks"] + run["ticks"]:
        if "next_ref" not in tick:
            continue
        rgb, dep = tick["next_ref"]
        for s, a in enumerate(tick["assignments"]):
            if a is None or s not in tick["next_live"]:
                continue
            sess, idxs = a
            out[(sess.sid, idxs[-1] + 1)] = (rgb, dep, s)
    return out


def compare(run: dict, viewers: Viewers, cfg: dict, weights: dict,
            seed: int, count: int, precision: str = "highest",
            control: Optional[str] = None) -> dict:
    """Readings of the compared numbers over the sampled windows: the
    program's frames against the reference, or, with ``control`` set,
    the reference computed at that precision against the reference.

    ``frame_err_p99`` is the 99th percentile of the colour error over
    every pixel of the sampled frames (a single pixel whose z-buffer tie
    breaks the other way reads far above it); ``hole_err_max`` the largest
    over the reference's settled hole pixels, the pixels that only hole
    compaction, the hole-stage gather and its fallback, the decoder and
    compositing produce; ``ref_rgb_err`` and ``ref_depth_err`` the largest
    over the co-rendered next reference frames."""
    import reference

    arch = load_arch(cfg)
    cam = reference.Camera(cfg["res"], cfg["fov_deg"])
    refs = next_refs(run)
    picked = pick_windows(run["records"], count, seed)
    frame_err, ref_rgb_err, ref_dep_err = [], 0.0, 0.0
    hole_err, settled = 0.0, 0
    hole_gap = 0
    for rec in picked:
        poses = viewers.poses[rec["sid"]]
        want = reference.render_window(arch, weights, cfg, cam, poses,
                                       rec["start"], rec["count"], precision)
        if control is None:
            got_frames = rec["frames"]
            got_holes = rec["hole_counts"]
            key = (rec["sid"], rec["start"])
            got_ref = None
            if key in refs:
                rgb, dep, s = refs[key]
                got_ref = (np.asarray(rgb[s]).reshape(-1, 3),
                           np.asarray(dep[s]).reshape(-1))
        else:
            got = reference.render_window(arch, weights, cfg, cam, poses,
                                          rec["start"], rec["count"],
                                          control)
            got_frames, got_holes = got["frames"], got["hole_counts"]
            got_ref = (got["ref_rgb"], got["ref_depth"])
        err = np.abs(got_frames - want["frames"])
        frame_err.append(err.reshape(-1))
        for f, pix in enumerate(want["settled"]):
            settled += len(pix)
            if len(pix):
                hole_err = max(hole_err, float(np.max(err[f, pix])))
        hole_gap = max(hole_gap, max(abs(a - b) for a, b in
                                     zip(got_holes, want["hole_counts"])))
        if got_ref is not None:
            ref_rgb_err = max(ref_rgb_err, float(np.max(np.abs(
                got_ref[0] - want["ref_rgb"]))))
            ref_dep_err = max(ref_dep_err, float(np.max(np.abs(
                got_ref[1] - want["ref_depth"]))))
    errs = np.concatenate(frame_err) if frame_err else np.zeros(1)
    return {
        "windows_checked": len(picked),
        "frame_err_p99": float(np.percentile(errs, 99)),
        "frame_err_max": float(np.max(errs)),
        "hole_err_max": hole_err,
        "settled_hole_pixels": settled,
        "ref_rgb_err": ref_rgb_err,
        "ref_depth_err": ref_dep_err,
        "hole_gap_max": int(hole_gap),
        "finite": bool(np.isfinite(errs).all()),
    }


def judge(readings: dict, limits: dict, missing: int) -> Dict[str, dict]:
    """Each compared number beside its limit."""
    checks = {name: {"value": readings[name], "limit": limit}
              for name, limit in limits.items() if name in readings}
    checks["missing_frames"] = {"value": missing, "limit": 0}
    return checks


def passed(checks: Dict[str, dict], readings: dict) -> bool:
    return readings["finite"] and readings["windows_checked"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def percentile(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if values else None


def end_to_end(run: dict, setup_s: float) -> Dict[str, Optional[float]]:
    window_s = run["t_end"] - run["t_open"]
    recs = [r for r in run["records"] if r["in_window"]]
    lat = [r["latency_s"] for r in recs for _ in range(r["count"])]
    first = [r["latency_s"] for r in recs if r["first"]]
    return {
        "frames_per_s": sum(r["count"] for r in recs) / window_s,
        "frame_latency_p95_s": percentile(lat, 95),
        "first_frame_p95_s": percentile(first, 95),
        "setup_s": setup_s,
    }


def run_record(run: dict, cfg: dict, device: dict) -> dict:
    """What the per-layer metric readers read: counters, host stamps and
    the required work of every tick in the window."""
    import peaks
    import work

    arch = load_arch(cfg)
    hw = run["hw"]
    ns = cfg["num_samples"]
    bucket = cfg["pool_bucket"]
    ticks = []
    for t in run["ticks"]:
        live = sum(a is not None for a in t["assignments"])
        hole_rays = min(t["hole_total"], bucket * live)
        ref_rays = hw * len(t["next_live"])
        ticks.append({
            "hole_rays": hole_rays, "ref_rays": ref_rays,
            "primed_rays": hw * len(t["admitted"]),
            "frames": t["frames_live"], "live_slots": live,
            "rit": t["rit"].tolist(),
            "work": work.tick_work(arch, cfg, hole_rays, ref_rays,
                                   t["frames_live"], live),
            "prime_work": work.tick_work(arch, cfg, 0,
                                         hw * len(t["admitted"]), 0, 0),
            "gather_work": arch.gather_work(cfg, (hole_rays + ref_rays) * ns),
            "mlp_work": arch.decoder_work(cfg, (hole_rays + ref_rays) * ns),
        })
    recs = [r for r in run["records"] if r["in_window"]]
    return {
        "config": cfg, "device": device,
        "peaks": peaks.PEAKS.get(device["kind"]),
        "window_s": run["t_end"] - run["t_open"], "ticks": ticks,
        "frames": sum(r["count"] for r in recs),
        "hole_fractions": [h / hw for r in recs for h in r["hole_counts"]],
        "queue_waits_s": [r["admitted_s"] - r["submitted_s"]
                          for r in recs if r["first"]],
        "memory_peak_bytes": device.get("memory_peak_bytes"),
    }


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run(cell: dict, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, fault: Optional[Callable] = None,
        control: Optional[str] = None) -> dict:
    """One run of ``cell``; returns the result line as a dict. ``fault``
    (tests only) rewrites each tick's result as it is dispatched;
    ``control`` (``bench/control.py``) also reads the compared numbers of
    the reference computed at that precision and judges them by the
    cell's limits (``"control"``, ``"control_checks"``,
    ``"control_correct"``)."""
    import jax

    if require_chip:
        check_device(cell["chips"])

    cfg = cell["config"]
    clock = CompileClock()
    weights = jax.block_until_ready(load_arch(cfg).make_weights(cfg, seed))
    engine = build_engine(cfg, weights)
    viewers = Viewers(cell["mix"], cfg["window"], seed)
    trace_dir = None
    if trace:
        import peaks

        peaks.peaks_for(device_info()["kind"])  # an unknown chip is an error
        trace_dir = ROOT / ".bench_trace" / f"{cell['name']}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)

    run_ = serve_window(engine, viewers, cfg, seconds, trace_dir, clock,
                        fault)
    setup_s = run_["t_setup_end"] - T_START
    device = device_info()
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    attempted = sum(len(a[1]) for t in run_["ticks"]
                    for a in t["assignments"] if a is not None)
    delivered = sum(r["count"] for r in run_["records"] if r["in_window"])
    record = run_record(run_, cfg, device)
    log(f"window: {len(run_['ticks'])} ticks, {delivered} frames in "
        f"{record['window_s']:.3f} s; set-up {setup_s:.3f} s; compiles: "
        f"{clock.count} ({clock.seconds:.1f} s), "
        f"{run_['compiles_window']} inside the window")
    # free the program's state before the reference runs
    for t in run_["ticks"] + run_["warm_ticks"]:
        if "next_ref" in t:
            rgb, dep = t["next_ref"]
            t["next_ref"] = (np.asarray(rgb), np.asarray(dep))
    del engine
    gc.collect()
    readings = compare(run_, viewers, cfg, weights, seed,
                       cell["mix"]["check_windows"])
    checks = judge(readings, cell["limits"], attempted - delivered)
    correct = passed(checks, readings)
    log(f"readings: {json.dumps(readings)}")

    if trace:
        import trace_reduce

        files = sorted(trace_dir.glob("**/*.xplane.pb"))
        reduced = trace_reduce.reduce(str(files[-1]),
                                      (run_["t_open"], run_["t_end"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        metrics = {}
        for m in cell["per_layer"]:
            value = metric_reader(m["name"])(record, reduced)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out = {"breakdown": {"device_ops": trace_reduce.top_ops(reduced),
                             "idle_gaps": reduced["idle_gaps"]}}
    else:
        e2e = end_to_end(run_, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]
                   if e2e.get(m["name"]) is not None}
        out = {}
    result = {"correct": correct, "attempted": attempted,
              "failed": attempted - delivered, "metrics": metrics,
              "device": device}
    result.update(out)
    if control is not None:
        ctl = compare(run_, viewers, cfg, weights, seed,
                      cell["mix"]["check_windows"], control=control)
        ctl_checks = judge(ctl, cell["limits"], 0)
        result.update(readings=readings, control=ctl,
                      control_checks=ctl_checks,
                      control_correct=passed(ctl_checks, ctl))
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log("run_cell: the system under test (src/repro) is not in this "
            "checkout")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run
    # writes only inside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    try:
        cell = load_cell(args.workload)
        # before the backend starts: a TPU host may fix the cache as the
        # backend comes up
        enable_cache(jax)
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"run_cell: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
