"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the device plane ``/device:TPU:<n>`` has a line ``XLA Modules``
(one event per program run, named ``jit_<function>(<fingerprint>)``) and
a line ``XLA Ops`` (one event per HLO instruction run, named by its HLO
text ``%<name>.<k> = ...``; a Pallas kernel is a custom call named after
the kernel's jitted function, e.g. ``%fused_gather_dual.1``). Event times
are nanoseconds after the profile start, which the ``Task Environment``
plane gives as ``profile_start_time`` (nanoseconds since the epoch), so a
window measured on the host's clock maps onto the trace.

The benchmark's own host spans (``TraceAnnotation`` names starting with
``bench.``) appear on the host plane and name what the host was doing in
each device idle gap.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."


def module_name(event_name: str) -> str:
    """``jit__tick_streaming(123)`` -> ``_tick_streaming``."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%fused_gather_dual.1 = (...) custom-call(...)`` ->
    ``fused_gather_dual`` (the instruction name without its ``.k``)."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def instruction_label(event_name: str) -> str:
    """``%fusion.12 = f32[8,12]{1,0} fusion(...)`` -> ``fusion.12
    f32[8,12]``: the instruction and its result's shape."""
    name, _, rest = event_name.partition(" = ")
    shape = re.split(r"[{ ]", rest, maxsplit=1)[0] if rest else ""
    return f"{name.lstrip('%')} {shape}".strip()


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _profile_start_ns(pd) -> Optional[int]:
    for plane in pd.planes:
        if plane.name == "Task Environment":
            for key, value in plane.stats:
                if key == "profile_start_time":
                    return int(value)
    return None


def reduce(path: str, window_epoch_s: Optional[Tuple[float, float]] = None
           ) -> Dict[str, object]:
    """Device busy time, per-module and per-op device time, and the idle
    gaps, within ``window_epoch_s`` (host epoch seconds; the whole trace
    when None). Times are in seconds, averaged over the TPU devices seen.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    start_ns = _profile_start_ns(pd)
    if window_epoch_s is not None and start_ns is not None:
        lo = window_epoch_s[0] * 1e9 - start_ns
        hi = window_epoch_s[1] * 1e9 - start_ns
    else:
        lo, hi = float("-inf"), float("inf")

    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    modules: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"count": 0, "seconds": 0.0})
    ops: Dict[str, float] = defaultdict(float)
    instructions: Dict[str, float] = defaultdict(float)
    busy_ns = 0.0
    first, last = float("inf"), float("-inf")
    gaps_all: List[Tuple[float, float]] = []
    for plane in devices:
        op_intervals = []
        for line in plane.lines:
            if line.name not in (MODULE_LINE, OPS_LINE):
                continue
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                a, b = max(a, lo), min(b, hi)
                if b <= a:
                    continue
                if line.name == MODULE_LINE:
                    m = modules[module_name(ev.name)]
                    m["count"] += 1
                    m["seconds"] += (b - a) / 1e9
                else:
                    ops[op_name(ev.name)] += (b - a) / 1e9
                    instructions[instruction_label(ev.name)] += (b - a) / 1e9
                    op_intervals.append((a, b))
                    first, last = min(first, a), max(last, b)
        merged = _union(op_intervals)
        busy_ns += sum(b - a for a, b in merged)
        gaps_all += [(merged[i][1], merged[i + 1][0])
                     for i in range(len(merged) - 1)]
    n = max(len(devices), 1)
    if window_epoch_s is not None:
        window_s = window_epoch_s[1] - window_epoch_s[0]
    else:
        window_s = (last - first) / 1e9 if last > first else 0.0
    for m in modules.values():
        m["seconds"] /= n
    return {
        "devices": len(devices),
        "window_s": window_s,
        "busy_s": busy_ns / 1e9 / n,
        "modules": dict(modules),
        "ops": {k: v / n for k, v in ops.items()},
        "instructions": {k: v / n for k, v in instructions.items()},
        "idle_gaps": _label_gaps(pd, gaps_all),
    }


def _label_gaps(pd, gaps: List[Tuple[float, float]], top: int = 10
                ) -> List[List[object]]:
    """The ``top`` longest device idle gaps, each named by the benchmark
    host span that overlaps it most (``host`` when none does)."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, label = 0.0, "host"
        for s0, s1, name in spans:
            overlap = min(b, s1) - max(a, s0)
            if overlap > best:
                best, label = overlap, name
        out.append([label, (b - a) / 1e9])
    return out


def top_ops(reduced: Dict[str, object], top: int = 10) -> List[List[object]]:
    """The ``top`` device instructions by device time, with their shapes
    (XLA names most fusions ``fusion.k``: the shape tells them apart)."""
    ops = reduced["instructions"]
    return [[k, ops[k]] for k in sorted(ops, key=ops.get, reverse=True)[:top]]
