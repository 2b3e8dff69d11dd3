"""Per-stage device time and host spans of a traced serving window.

    python3 bench/stage_trace.py --workload <cell> --seed <n> --seconds <s>

runs the cell's set-up and window as ``run_cell.py`` does, under the
profiler (on a TPU; no correctness check), and prints one JSON line: the
device seconds per tick of each stage of the serving programs, the mean
host seconds of ``step()``, the programs' recompiles inside the window,
every ``<program>/<stage>``'s seconds, the longest instructions with
their stages, the idle gaps named by the innermost host span, and the
fusions whose instructions mix stages.

The serving programs open a ``jax.named_scope`` per stage (``STAGES``).
The trace's ``/host:metadata`` plane holds, per program, the optimized
HLO module that ran (an ``Hlo Proto`` stat keyed by the module's event
name ``jit_<fn>(<fingerprint>)``), and each instruction's
``metadata={op_name="jit(<fn>)/<scope>/.../<primitive>"}`` carries the
scopes it was traced under. So every ``XLA Ops`` event maps to its
program (the ``XLA Modules`` run that holds it) and to the innermost stage
in its ``op_name``: a fusion is charged to its fused root's stage, only
leaf events count (a ``while`` or ``conditional`` holding other events is
not counted again), and an instruction under no stage scope is
``unscoped``. The plane is read with a protobuf wire reader and JAX's HLO
module parser; the engine's and the benchmark's host spans
(``serve.*``, ``bench.*``) share the device events' clock.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from trace_reduce import (  # noqa: E402
    MODULE_LINE, OPS_LINE, _profile_start_ns, _union, instruction_label,
    module_name)

HOST_SPAN_PREFIXES = ("bench.", "serve.")
METADATA_PLANE = "/host:metadata"
# the serving programs' stage scopes (``jax.named_scope`` in src/repro)
STAGES = ("warp", "compact", "rit_build", "gather", "rit_scatter",
          "rit_fallback", "decode", "composite", "dense_fallback")
UNSCOPED = "unscoped"
# the programs whose stages are read per tick: the fused serving tick and
# the admission prime
SERVE_PROGRAMS = ("_tick_streaming", "_prime_select")


# ---------------------------------------------------------------------------
# the programs' HLO, from the trace's metadata plane
# ---------------------------------------------------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of each field of a serialized protobuf
    message: an int for varint and fixed-width fields, a memoryview for
    length-delimited ones (sub-messages, strings, bytes)."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield field, value


def hlo_modules(path: str) -> Dict[str, bytes]:
    """Module event name (``jit_<fn>(<fingerprint>)``) -> its serialized
    ``HloModuleProto``, from the ``/host:metadata`` plane's ``Hlo Proto``
    stats (``XSpace.planes`` 1; ``XPlane.name`` 2, ``event_metadata`` 4,
    ``stat_metadata`` 5; ``XEventMetadata.name`` 2, ``stats`` 5;
    ``XStat.metadata_id`` 1, ``bytes_value`` 6; ``HloProto.hlo_module``
    1)."""
    with open(path, "rb") as f:
        space = f.read()
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in parts if k == 2), "")
        if name != METADATA_PLANE:
            continue
        stat_ids = set()
        for k, entry in parts:
            if k != 5:
                continue
            meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
            if bytes(meta.get(2, b"")) == b"Hlo Proto":
                stat_ids.add(meta.get(1, 0))
        out = {}
        for k, entry in parts:
            if k != 4:
                continue
            event = list(_fields(dict(_fields(entry)).get(2, b"")))
            ev_name = next((bytes(v).decode() for f, v in event if f == 2),
                           "")
            for f, stat in event:
                if f != 5:
                    continue
                st = dict(_fields(stat))
                if st.get(1) in stat_ids and 6 in st:
                    hlo = dict(_fields(st[6]))
                    if 1 in hlo:
                        out[ev_name] = bytes(hlo[1])
        return out
    return {}


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([^\s=]+) = ")
_CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')


def hlo_instructions(module_proto: bytes) -> Dict[str, Dict[str, object]]:
    """Computation -> instruction -> ``{"op_name", "calls", "root"}`` of a
    serialized ``HloModuleProto``, as JAX's HLO module parser prints it."""
    from jax._src.lib import xla_client

    text = xla_client._xla.HloModule.from_serialized_hlo_module_proto(
        module_proto).to_string()
    comps: Dict[str, Dict[str, object]] = {}
    current = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            current = comps.setdefault(m.group(1), {}) if m else None
            continue
        m = _INSTRUCTION.match(line) if current is not None else None
        if not m:
            continue
        op = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        current[m.group(2)] = {
            "op_name": (re.sub(r"\\(.)", r"\1", op.group(1)) if op
                        else ""),
            "calls": calls.group(1) if calls else None,
            "root": bool(m.group(1))}
    return comps


def op_names(module_proto: bytes) -> Dict[str, str]:
    """Instruction -> its ``op_name`` metadata, over every computation of
    a module (instructions without one left out)."""
    return {name: ins["op_name"]
            for comp in hlo_instructions(module_proto).values()
            for name, ins in comp.items() if ins["op_name"]}


def stage_of(op_name: str) -> str:
    """The innermost stage scope of an ``op_name``
    (``jit(f)/compact/jit(g)/gather/pallas_call`` -> ``gather``), or
    ``unscoped``. The last component is the primitive (``gather`` the
    primitive is no stage); of names merged with ``;``, the first."""
    parts = op_name.split(";", 1)[0].split("/")[:-1]
    return next((p for p in reversed(parts) if p in STAGES), UNSCOPED)


def instruction_stages(module_proto: bytes
                       ) -> Tuple[Dict[str, str], Dict[str, List[str]]]:
    """(instruction -> stage, fusion -> the stages its fused instructions
    come from where they are more than one) of a module. A fusion takes
    its fused root's stage, or its own where the root carries no op_name
    (a tuple)."""
    comps = hlo_instructions(module_proto)

    def root_op_name(comp: str) -> str:
        for ins in comps.get(comp, {}).values():
            if ins["root"]:
                if ins["calls"] in comps:
                    return root_op_name(ins["calls"])
                return ins["op_name"]
        return ""

    stages: Dict[str, str] = {}
    mixed: Dict[str, List[str]] = {}
    for comp in comps.values():
        for name, ins in comp.items():
            op = ins["op_name"]
            if ins["calls"] in comps:
                op = root_op_name(ins["calls"]) or op
                inner = {stage_of(i["op_name"])
                         for i in comps[ins["calls"]].values()
                         if i["op_name"]}
                if len(inner) > 1:
                    mixed[name] = sorted(inner)
            stages[name] = stage_of(op)
    return stages, mixed


# ---------------------------------------------------------------------------
# a trace's stages, host spans and idle gaps
# ---------------------------------------------------------------------------


def _leaves(events: List[Tuple[float, float, str]]
            ) -> List[Tuple[float, float, str]]:
    """The events that hold no other event (sorted by start, longest
    first, an event holds the next one when that starts before it
    ends)."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    return [e for i, e in enumerate(events)
            if i + 1 == len(events) or events[i + 1][0] >= e[1]]


def reduce(path: str, window_epoch_s: Optional[Tuple[float, float]] = None
           ) -> Dict[str, object]:
    """Within ``window_epoch_s`` (host epoch seconds; the whole trace when
    None): the runs per program; the leaf-op seconds per program and
    stage, and per instruction with its stage; the fusions that mix
    stages; the count and seconds of each host span name (spans that
    start in the window); and the ten longest device idle gaps, each named
    by a host span. Times are in seconds, averaged over the TPU devices
    seen; lists are longest first."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    start_ns = _profile_start_ns(pd)
    if window_epoch_s is not None and start_ns is not None:
        lo = window_epoch_s[0] * 1e9 - start_ns
        hi = window_epoch_s[1] * 1e9 - start_ns
    else:
        lo, hi = float("-inf"), float("inf")
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    hlo = _StageMaps(path)
    runs_per: Dict[str, int] = defaultdict(int)
    stages: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    mixed: Dict[str, List[object]] = {}
    instructions: Dict[str, List[object]] = {}
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        runs, op_events = [], []
        for line in plane.lines:
            if line.name in (MODULE_LINE, OPS_LINE):
                events = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name) for ev in line.events]
                (runs if line.name == MODULE_LINE else op_events).extend(
                    events)
        for a, b, name in runs:
            if min(b, hi) > max(a, lo):
                runs_per[module_name(name)] += 1
        _charge_stages(runs, op_events, lo, hi, hlo, stages, mixed,
                       instructions)
        busy = _union([(max(a, lo), min(b, hi)) for a, b, _ in op_events
                       if min(b, hi) > max(a, lo)])
        gaps += [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    n = max(len(devices), 1)
    spans = _host_spans(pd)
    return {
        "devices": len(devices),
        "runs": {k: v / n for k, v in runs_per.items()},
        "stages": {prog: {k: v / n for k, v in st.items()}
                   for prog, st in stages.items()},
        "mixed_fusions": _longest(mixed, n),
        "instructions": _longest(instructions, n),
        "host_spans": _span_totals(spans, lo, hi),
        "idle_gaps": _label_gaps(spans, gaps),
    }


class _StageMaps:
    """Module event name -> (instruction -> stage, mixed fusions), decoded
    from the trace's HLO protos the first time a module is asked for."""

    def __init__(self, path: str):
        self.path = path
        self.protos: Optional[Dict[str, bytes]] = None
        self.maps: Dict[str, Tuple[Dict[str, str], Dict[str, List[str]]]] = {}

    def __call__(self, module: str
                 ) -> Tuple[Dict[str, str], Dict[str, List[str]]]:
        if module not in self.maps:
            if self.protos is None:
                self.protos = hlo_modules(self.path)
            proto = self.protos.get(module)
            self.maps[module] = (instruction_stages(proto) if proto
                                 else ({}, {}))
        return self.maps[module]


def _longest(acc: Dict[str, List[object]], n: int
             ) -> List[List[object]]:
    """``[[key, what, seconds / n], ...]``, longest first."""
    return sorted(([k, v[0], v[1] / n] for k, v in acc.items()),
                  key=lambda m: -m[2])


def _charge_stages(runs: List[Tuple[float, float, str]],
                   op_events: List[Tuple[float, float, str]],
                   lo: float, hi: float, hlo: _StageMaps,
                   stages: Dict[str, Dict[str, float]],
                   mixed: Dict[str, List[object]],
                   instructions: Dict[str, List[object]]) -> None:
    """Adds each leaf op event's seconds within ``[lo, hi]`` to its
    program's stage (the program run whose interval holds the event's
    start) and to its instruction under ``<program>/<instruction
    label>``, with its stage; a fusion's whose fused instructions mix
    stages also to ``mixed``, with those stages."""
    runs = sorted(runs)
    starts = [r[0] for r in runs]
    for a, b, name in _leaves(op_events):
        k = bisect.bisect_right(starts, a) - 1
        if k < 0 or a >= runs[k][1]:
            continue
        t = (min(b, hi) - max(a, lo)) / 1e9
        if t <= 0:
            continue
        module = runs[k][2]
        instr = name.split(" = ", 1)[0].lstrip("%")
        stage_map, mixes = hlo(module)
        prog = module_name(module)
        stage = stage_map.get(instr, UNSCOPED)
        stages[prog][stage] += t
        key = f"{prog}/{instruction_label(name)}"
        instructions.setdefault(key, [stage, 0.0])[1] += t
        if instr in mixes:
            mixed.setdefault(key, [mixes[instr], 0.0])[1] += t


def _host_spans(pd) -> List[Tuple[float, float, str, int]]:
    """The benchmark's and the engine's host spans, each with its depth
    among them on its thread (0 = outermost)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name) for ev in line.events
                          if ev.name.startswith(HOST_SPAN_PREFIXES)),
                         key=lambda e: (e[0], -e[1]))
            open_ends: List[float] = []
            for a, b, name in evs:
                while open_ends and open_ends[-1] <= a:
                    open_ends.pop()
                out.append((a, b, name, len(open_ends)))
                open_ends.append(b)
    return out


def _span_totals(spans, lo: float, hi: float) -> Dict[str, Dict[str, float]]:
    """Per host span name, the count and seconds of the spans that start
    within ``[lo, hi)``."""
    out: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"count": 0, "seconds": 0.0})
    for a, b, name, _ in spans:
        if lo <= a < hi:
            out[name]["count"] += 1
            out[name]["seconds"] += (b - a) / 1e9
    return dict(out)


def _label_gaps(spans, gaps: List[Tuple[float, float]], top: int = 10
                ) -> List[List[object]]:
    """The ``top`` longest device idle gaps, each named by the innermost
    host span that overlaps it (the one that overlaps it most among the
    innermost; ``host`` when none does)."""
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, label = (-1, 0.0), "host"
        for s0, s1, name, depth in spans:
            overlap = min(b, s1) - max(a, s0)
            if overlap > 0 and (depth, overlap) > best:
                best, label = (depth, overlap), name
        out.append([label, (b - a) / 1e9])
    return out


def stage_breakdown(reduced: Dict[str, object]) -> List[List[object]]:
    """``[[<program>/<stage>, seconds], ...]`` over the window, longest
    first."""
    rows = [[f"{prog}/{stage}", t]
            for prog, st in reduced["stages"].items()
            for stage, t in st.items()]
    return sorted(rows, key=lambda r: -r[1])


def stage_per_tick(reduced: Dict[str, object], stage: str
                   ) -> Optional[float]:
    """Device seconds of the serving programs' leaf ops in ``stage`` (a
    name of ``STAGES`` or ``unscoped``) per tick in the window; None where
    the programs carry no stage scope or no tick ran."""
    spent = [reduced["stages"].get(p, {}) for p in SERVE_PROGRAMS]
    ticks = reduced["runs"].get("_tick_streaming")
    if not ticks or not any(k != UNSCOPED for st in spent for k in st):
        return None
    return sum(st.get(stage, 0.0) for st in spent) / ticks


def host_step_s(reduced: Dict[str, object]) -> Optional[float]:
    """Mean seconds of the engine's ``step()`` (its ``serve.step`` span)
    over the steps that started in the window."""
    step = reduced["host_spans"].get("serve.step")
    return step["seconds"] / step["count"] if step else None


def report(reduced: Dict[str, object]) -> Dict[str, object]:
    """The numbers the per-stage metrics read, by metric name."""
    out = {f"stage_s.{s}": stage_per_tick(reduced, s)
           for s in STAGES + (UNSCOPED,) if s != "dense_fallback"}
    out["host_step_s"] = host_step_s(reduced)
    return out


# ---------------------------------------------------------------------------
# a traced window of a cell
# ---------------------------------------------------------------------------


class _WindowProbe:
    """Takes the engine's jit-cache sizes when the set-up tick has been
    dispatched (``serve_window``'s per-dispatch hook, which returns each
    result as it is), so that ``recompiles()`` counts what the window
    compiled."""

    def __init__(self):
        self.probe = None

    def __call__(self, engine, result):
        if self.probe is None:
            from repro.analysis.jitprobe import JitCacheProbe

            self.probe = JitCacheProbe(engine.engine)
        return result


def main(argv=None) -> int:
    import run_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run
    # writes only inside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    cell = run_cell.load_cell(args.workload)
    run_cell.enable_cache(jax)
    try:
        run_cell.check_device(cell["chips"])
    except run_cell.NoChip as e:
        run_cell.log(f"stage_trace: {e}")
        return 2
    cfg = cell["config"]
    clock = run_cell.CompileClock()
    engine = run_cell.build_engine(cfg, jax.block_until_ready(
        run_cell.load_arch(cfg).make_weights(cfg, args.seed)))
    viewers = run_cell.Viewers(cell["mix"], cfg["window"], args.seed)
    trace_dir = BENCH.parent / ".bench_trace" / f"{cell['name']}.stages"
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    probe = _WindowProbe()
    run = run_cell.serve_window(engine, viewers, cfg, args.seconds,
                                trace_dir, clock, fault=probe)
    t0 = time.time()
    path = str(sorted(trace_dir.glob("**/*.xplane.pb"))[-1])
    reduced = reduce(path, (run["t_open"], run["t_end"]))
    shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = report(reduced)
    metrics["window_compiles"] = probe.probe.recompiles()
    frames = sum(r["count"] for r in run["records"] if r["in_window"])
    print(json.dumps({
        "device": run_cell.device_info(),
        "window_s": run["t_end"] - run["t_open"],
        "ticks": len(run["ticks"]), "frames": frames,
        "reduce_s": time.time() - t0, "metrics": metrics,
        "runs": reduced["runs"],
        "stages": stage_breakdown(reduced),
        "idle_gaps": reduced["idle_gaps"],
        "host_spans": reduced["host_spans"],
        "instructions": reduced["instructions"][:20],
        "mixed_fusions": reduced["mixed_fusions"][:20]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
