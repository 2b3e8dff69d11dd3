"""``stage_trace`` on a scoped trace recorded on a TPU v5e
(``data/scoped_trace.xplane.pb``, written by ``record_scoped_trace.py``):
three runs of ``_tick_probe`` (``warp`` and ``gather`` scopes, a
``fused_gather_dual`` kernel), two of ``_prime_probe`` (a ``while`` under
``warp``) with a ``serve.step`` span nested in a ``bench.step`` span
between them, and one ``fused_nerf_mlp`` call. The stage arithmetic is
checked against an independent walk of the trace's events."""
from __future__ import annotations

from pathlib import Path

import pytest

import stage_trace

DATA = Path(__file__).parent / "data"
SCOPED = str(DATA / "scoped_trace.xplane.pb")
SMALL = str(DATA / "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return stage_trace.reduce(SCOPED)


def _device_events():
    """(program runs, op events) of the trace's one TPU, as
    ``(start, end, name)``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(SCOPED)
    (plane,) = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    lines = {line.name: [(ev.start_ns, ev.start_ns + ev.duration_ns,
                          ev.name) for ev in line.events]
             for line in plane.lines}
    return lines["XLA Modules"], lines["XLA Ops"]


def _by_program(runs, ops):
    """Each op event with the program whose run holds its start, and
    whether another op event lies inside it."""
    out = []
    for a, b, name in ops:
        prog = next(stage_trace.module_name(r[2]) for r in runs
                    if r[0] <= a < r[1])
        holds = any(a <= a2 and b2 <= b and (a2, b2) != (a, b)
                    for a2, b2, _ in ops)
        out.append((prog, a, b, holds))
    return out


def test_wire_reader_matches_tensorflow():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")

    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(SCOPED).read_bytes())
    (plane,) = [p for p in space.planes
                if p.name == stage_trace.METADATA_PLANE]
    want = {}
    for meta in plane.event_metadata.values():
        proto = hlo_pb2.HloProto()
        proto.ParseFromString(meta.stats[0].bytes_value)
        want[meta.name] = {ins.name: ins.metadata.op_name
                           for comp in proto.hlo_module.computations
                           for ins in comp.instructions
                           if ins.metadata.op_name}
    got = {name: stage_trace.op_names(proto)
           for name, proto in stage_trace.hlo_modules(SCOPED).items()}
    assert set(got) == set(want) and len(got) >= 3
    for name in want:
        assert got[name] == want[name], name


def test_stages_cover_each_programs_leaf_time(reduced):
    runs, ops = _device_events()
    leaf = {}
    for prog, a, b, holds in _by_program(runs, ops):
        if not holds:
            leaf[prog] = leaf.get(prog, 0.0) + (b - a) / 1e9
    assert set(reduced["stages"]) == set(leaf)
    for prog, seconds in leaf.items():
        assert sum(reduced["stages"][prog].values()) == pytest.approx(
            seconds, abs=1e-9)


def test_nested_while_is_counted_once(reduced):
    runs, ops = _device_events()
    prime = [e for e in _by_program(runs, ops) if e[0] == "_prime_probe"]
    held = sum((b - a) / 1e9 for _, a, b, holds in prime if holds)
    every = sum((b - a) / 1e9 for _, a, b, _ in prime)
    # the loop is one event per run that holds its body's events
    assert sum(holds for *_, holds in prime) == 2 and held > 0
    assert sum(reduced["stages"]["_prime_probe"].values()) == pytest.approx(
        every - held, abs=1e-9)


def test_scopes_read_back_and_fusions_charged_by_root(reduced):
    # XLA fused the warp-scoped matmul and tanh, the gather-scoped sin and
    # the unscoped final sum into one fusion: charged to its root, the sum
    tick = reduced["stages"]["_tick_probe"]
    assert set(tick) == {"gather", stage_trace.UNSCOPED}
    (mix,) = reduced["mixed_fusions"]
    assert mix[0].startswith("_tick_probe/fusion.")
    assert mix[1] == ["gather", "unscoped", "warp"]
    assert 0 < mix[2] <= tick[stage_trace.UNSCOPED]
    # the loop's body ran under warp
    prime = reduced["stages"]["_prime_probe"]
    assert prime["warp"] > 0.9 * sum(prime.values())
    assert reduced["runs"] == {"_tick_probe": 3, "_prime_probe": 2,
                               "_lambda": 1}
    assert set(reduced["stages"]["_lambda"]) == {stage_trace.UNSCOPED}


def test_kernel_charged_to_its_scope():
    (tick,) = [p for name, p in stage_trace.hlo_modules(SCOPED).items()
               if name.startswith("jit__tick_probe(")]
    stages, _ = stage_trace.instruction_stages(tick)
    kernels = [k for k in stages if k.startswith("fused_gather_dual")]
    assert kernels and all(stages[k] == "gather" for k in kernels)


def test_gap_in_a_serve_span_is_named_by_it(reduced):
    # the 70 ms between the primes lies in bench.step; its first 50 ms in
    # serve.step, which the gap is named by, though bench.step overlaps
    # it more
    gaps = dict((round(t, 2), name) for name, t in reduced["idle_gaps"])
    assert gaps[0.2] == "host"
    (t,) = [t for t in gaps if 0.07 <= t < 0.09]
    assert gaps[t] == "serve.step"


def test_host_spans_counted(reduced):
    spans = reduced["host_spans"]
    assert spans["serve.step"]["count"] == 1
    assert 0.05 <= spans["serve.step"]["seconds"] < 0.06
    assert spans["bench.step"]["count"] == 1
    assert stage_trace.host_step_s(reduced) == spans["serve.step"]["seconds"]


def test_a_trace_without_scopes_reads_unscoped():
    plain = stage_trace.reduce(SMALL)
    assert plain["runs"]["_tick_probe"] == 3
    assert all(set(st) == {stage_trace.UNSCOPED}
               for st in plain["stages"].values())
    assert plain["host_spans"] == {} and plain["mixed_fusions"] == []


def test_window_clips_stages(reduced):
    from jax.profiler import ProfileData

    runs, _ = _device_events()
    start = stage_trace._profile_start_ns(ProfileData.from_file(SCOPED))
    first = min(r for r in runs if "_tick_probe" in r[2])
    lo = (start + first[0]) / 1e9
    clipped = stage_trace.reduce(SCOPED, (lo, lo + (first[1] - first[0])
                                          / 1e9 + 1e-6))
    assert clipped["runs"] == {"_tick_probe": 1}
    assert set(clipped["stages"]) == {"_tick_probe"}
    total = sum(clipped["stages"]["_tick_probe"].values())
    assert 0 < total < sum(reduced["stages"]["_tick_probe"].values()) / 2


@pytest.mark.parametrize("op_name, stage", [
    ("jit(_tick_streaming)/compact/jit(f)/gather/pallas_call", "gather"),
    # the last component is the primitive: gather the primitive is no stage
    ("jit(_tick_streaming)/gather", "unscoped"),
    ("jit(_tick_streaming)/warp/vmap(vmap())/gather", "warp"),
    ("jit(_prime_select)/while/body/closed_call/rit_scatter/scatter",
     "rit_scatter"),
    # names merged with ";" take the first
    ("jit(t)/composite/reshape;jit(t)/warp/reshape", "composite"),
    ("jit(t)/dense_fallback/cond/branch_1_fun/decode/add", "decode"),
    ("", "unscoped"),
])
def test_stage_of(op_name, stage):
    assert stage_trace.stage_of(op_name) == stage


STAGES = {"_tick_streaming": {"warp": 0.5, "compact": 0.25, "rit_build": 0.5,
                              "gather": 0.75, "rit_scatter": 3.0,
                              "rit_fallback": 2.0, "decode": 0.125,
                              "composite": 0.375, "dense_fallback": 0.0625,
                              "unscoped": 0.25},
          "_prime_select": {"compact": 0.125, "rit_build": 0.25,
                            "gather": 0.125, "rit_scatter": 1.0,
                            "rit_fallback": 0.5, "unscoped": 0.0625},
          "_other": {"warp": 4.0, "unscoped": 4.0}}
WINDOW = {"runs": {"_tick_streaming": 2, "_prime_select": 1, "_other": 9},
          "stages": STAGES,
          "host_spans": {"serve.step": {"count": 4, "seconds": 0.01}}}


def test_report_per_tick_of_the_serving_programs():
    got = stage_trace.report(WINDOW)
    # (tick + prime) over the window's 2 ticks; another program's stages
    # are not the serving programs'
    want = {"warp": 0.5, "compact": 0.375, "rit_build": 0.75,
            "gather": 0.875, "rit_scatter": 4.0, "rit_fallback": 2.5,
            "decode": 0.125, "composite": 0.375, "unscoped": 0.3125}
    assert got == {**{f"stage_s.{k}": v / 2 for k, v in want.items()},
                   "host_step_s": 0.0025}
    # with the dense fallback's seconds the readings cover both programs'
    # leaf time
    covered = sum(v for k, v in got.items() if k.startswith("stage_s."))
    dense = stage_trace.stage_per_tick(WINDOW, "dense_fallback")
    leaf = sum(sum(STAGES[p].values()) for p in stage_trace.SERVE_PROGRAMS)
    assert covered + dense == pytest.approx(leaf / 2, rel=1e-12)
    assert stage_trace.stage_breakdown(WINDOW)[0] == ["_other/warp", 4.0]


def test_report_reads_nothing_without_scopes_or_ticks():
    unscoped = dict(WINDOW, stages={
        p: {"unscoped": sum(st.values())} for p, st in STAGES.items()})
    no_ticks = dict(WINDOW, runs={"_prime_select": 1})
    for window in (unscoped, no_ticks):
        assert all(v is None for k, v in stage_trace.report(window).items()
                   if k.startswith("stage_s."))
    assert stage_trace.host_step_s(dict(WINDOW, host_spans={})) is None
