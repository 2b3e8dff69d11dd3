"""Each per-layer metric's reader (``bench/metrics/<name>.py``) on a run
record and a trace reduction made by hand: the arithmetic, and nothing
returned where there is nothing to read."""
from __future__ import annotations

import json

import pytest

import peaks
import work
from bench_cells import ROOT

CFG = {"grid_res": 16, "mvoxel_edge": 8, "channels": 12, "num_samples": 16,
       "res": 4, "mlp_hidden": 64, "decoder": "mlp"}
PEAKS = peaks.peaks_for("TPU v5 lite")


def _tick(hole_rays, ref_rays, admitted):
    samples = (hole_rays + ref_rays) * CFG["num_samples"]
    return {"work": work.tick_work(CFG, hole_rays, ref_rays, 8, 2),
            "prime_work": work.tick_work(CFG, 0, 16 * admitted, 0, 0),
            "gather_work": work.gather_work(CFG, samples),
            "mlp_work": work.mlp_work(CFG, samples),
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


def _roofline(key):
    return sum(peaks.roofline_s(t[key]["flops"], t[key]["bytes"], PEAKS)[0]
               for t in RUN["ticks"])


def _expected():
    need = sum(peaks.roofline_s(w["flops"], w["bytes"], PEAKS)[0]
               for t in RUN["ticks"] for w in (t["work"], t["prime_work"])
               if w["flops"])
    return {
        "queue_wait_p95_s": 19.0,
        "fused_tick_s": 4.0,
        "prime_s": 0.5,
        "hole_fraction": 2.0,
        "rit_overflow_share.ref": 100.0 * 10 / 80,
        "rit_overflow_share.hole": 100.0 * 6 / 20,
        "fused_gather_dual_roofline": 100.0 * _roofline("gather_work") / 0.25,
        "fused_nerf_mlp_roofline": 100.0 * _roofline("mlp_work") / 0.125,
        "tick_mfu": 100.0 * need / 8.5,
        "device_idle_share": 10.0,
        "peak_hbm_gb": 1.5,
    }


def _names():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [m["name"] for m in bench["per_layer"]]


def test_every_metric_has_a_case():
    # and one more: the MLP kernel's reader, kept for the MLP cell
    assert set(_names()) | {"fused_nerf_mlp_roofline"} == set(_expected())


@pytest.mark.parametrize("name", sorted(_expected()))
def test_reader_arithmetic(name):
    import run_cell

    value = run_cell.metric_reader(name)(RUN, TRACE)
    assert value == pytest.approx(_expected()[name], rel=1e-12)


@pytest.mark.parametrize("name", ["fused_tick_s", "prime_s",
                                  "fused_gather_dual_roofline",
                                  "fused_nerf_mlp_roofline", "tick_mfu"])
def test_nothing_to_read_gives_nothing(name):
    import run_cell

    assert run_cell.metric_reader(name)(RUN, EMPTY) is None


def test_direct_decoder_has_no_mlp_roofline():
    import run_cell

    run = dict(RUN, config=dict(CFG, decoder="direct"))
    assert run_cell.metric_reader("fused_nerf_mlp_roofline")(run, TRACE) \
        is None
