"""Each per-layer metric's reader (``bench/metrics/<name>.py``) on a run
record and a trace reduction made by hand: the arithmetic, and nothing
returned where there is nothing to read.

The cases live beside the readers, one file per metric in
``metric_cases/<name>.py``: ``expected(run, trace)``, the reading of the
shared ``RUN`` and ``TRACE`` below; optionally ``extend(run)``, the run
record with what the reader needs beyond the shared one; and optionally
``NOTHING``, named ways ``(run, trace, empty) -> (run, trace)`` to make
records in which the reader finds nothing. A new metric is a reader, a
case file and its ``BENCHMARK.json`` entry."""
from __future__ import annotations

import importlib.util
import json

import pytest

import peaks
import work
from bench_cells import BENCH, ROOT

CASES = BENCH / "tests" / "metric_cases"
CFG = {"arch": "dvgo_dense", "grid_res": 16, "mvoxel_edge": 8,
       "channels": 12, "num_samples": 16, "res": 4, "mlp_hidden": 64,
       "decoder": "mlp"}
PEAKS = peaks.peaks_for("TPU v5 lite")


def _tick(hole_rays, ref_rays, admitted):
    import run_cell

    arch = run_cell.load_arch(CFG)
    samples = (hole_rays + ref_rays) * CFG["num_samples"]
    return {"work": work.tick_work(arch, CFG, hole_rays, ref_rays, 8, 2),
            "prime_work": work.tick_work(arch, CFG, 0, 16 * admitted, 0, 0),
            "gather_work": arch.gather_work(CFG, samples),
            "mlp_work": arch.decoder_work(CFG, samples),
            "rit": [[3, 10], [5, 40]]}


RUN = {"config": CFG, "peaks": PEAKS, "window_s": 10.0,
       "ticks": [_tick(2, 16, 0), _tick(4, 16, 1)],
       "hole_fractions": [0.01, 0.03],
       "queue_waits_s": [float(i) for i in range(21)],
       "memory_peak_bytes": 1_500_000_000}
TRACE = {"window_s": 10.0, "busy_s": 9.0,
         "modules": {"_tick_streaming": {"count": 2, "seconds": 8.0},
                     "_prime_select": {"count": 1, "seconds": 0.5}},
         "ops": {"fused_gather_dual": 0.25, "fused_nerf_mlp": 0.125}}
EMPTY = {"window_s": 10.0, "busy_s": 9.0, "modules": {}, "ops": {}}

NAMES = sorted(p.stem for p in CASES.glob("*.py"))


def case(name: str):
    path = CASES / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"metric_case_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(mod) -> dict:
    return mod.extend(RUN) if hasattr(mod, "extend") else RUN


def test_every_metric_has_a_case():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    registered = {m["name"] for m in bench["per_layer"]}
    assert registered <= set(NAMES), registered - set(NAMES)
    readers = {p.stem for p in (BENCH / "metrics").glob("*.py")}
    assert set(NAMES) <= readers, set(NAMES) - readers


@pytest.mark.parametrize("name", NAMES)
def test_reader_arithmetic(name):
    import run_cell

    mod = case(name)
    run = _run(mod)
    value = run_cell.metric_reader(name)(run, TRACE)
    assert value == pytest.approx(mod.expected(run, TRACE), rel=1e-12)


NOTHING = [(name, how) for name in NAMES
           for how in sorted(getattr(case(name), "NOTHING", {}))]


@pytest.mark.parametrize("name,how", NOTHING,
                         ids=[f"{n}-{h}" for n, h in NOTHING])
def test_nothing_to_read_gives_nothing(name, how):
    import run_cell

    mod = case(name)
    run, trace = mod.NOTHING[how](_run(mod), TRACE, EMPTY)
    assert run_cell.metric_reader(name)(run, trace) is None
