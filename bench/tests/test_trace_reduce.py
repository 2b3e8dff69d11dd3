"""``trace_reduce`` on a small trace recorded on a TPU v5e
(``data/small_trace.xplane.pb``, written by ``record_trace.py``): three
runs of ``_tick_probe`` (a ``fused_gather_dual`` kernel and a matmul), two
of ``_prime_probe`` and one ``fused_nerf_mlp`` call. The expected numbers
were read by hand from the trace's event list."""
from __future__ import annotations

from pathlib import Path

import pytest

import trace_reduce

TRACE = str(Path(__file__).parent / "data" / "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_modules_counted_and_timed(reduced):
    m = reduced["modules"]
    assert m["_tick_probe"]["count"] == 3
    # 107531 + 107296 + 107257 ns
    assert m["_tick_probe"]["seconds"] == pytest.approx(322084e-9, abs=1e-12)
    assert m["_prime_probe"]["count"] == 2
    assert m["_prime_probe"]["seconds"] == pytest.approx(29624e-9, abs=1e-12)
    assert m["_lambda"]["count"] == 1
    assert reduced["devices"] == 1


def test_kernels_named_by_their_function(reduced):
    ops = reduced["ops"]
    assert "fused_gather_dual" in ops and "fused_nerf_mlp" in ops
    assert 0 < ops["fused_gather_dual"] < reduced["modules"]["_tick_probe"][
        "seconds"]
    assert trace_reduce.op_name(
        "%fused_gather_dual.1 = (f32[1,8]) custom-call(f32[8] %copy)"
    ) == "fused_gather_dual"
    assert trace_reduce.module_name("jit__tick_streaming(123)") \
        == "_tick_streaming"


def test_busy_is_the_union_of_op_intervals(reduced):
    # ops never overlap on this trace's one core: busy equals their sum,
    # and it is below the modules' total (a module spans its own gaps)
    assert reduced["busy_s"] == pytest.approx(sum(reduced["ops"].values()),
                                              rel=1e-9)
    total_modules = sum(m["seconds"] for m in reduced["modules"].values())
    assert reduced["busy_s"] <= total_modules
    assert reduced["busy_s"] < reduced["window_s"]


def test_window_clips_events(reduced):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(TRACE)
    start = trace_reduce._profile_start_ns(pd)
    # the first tick runs from 45866652 ns for 107531 ns after the start
    lo = (start + 45866652) / 1e9
    clipped = trace_reduce.reduce(TRACE, (lo, lo + 50e-6))
    assert clipped["modules"]["_tick_probe"]["count"] == 1
    # host epoch seconds as a float resolve about 0.24 us at this epoch
    assert clipped["modules"]["_tick_probe"]["seconds"] == pytest.approx(
        50e-6, abs=5e-7)
    assert "_prime_probe" not in clipped["modules"]
    assert clipped["window_s"] == pytest.approx(50e-6, abs=5e-7)


def test_idle_gaps_longest_first(reduced):
    gaps = reduced["idle_gaps"]
    assert 0 < len(gaps) <= 10
    secs = [g[1] for g in gaps]
    assert secs == sorted(secs, reverse=True)
    # the 0.2 s sleep between the ticks and the primes is the longest gap
    assert 0.2 <= secs[0] < 0.21
