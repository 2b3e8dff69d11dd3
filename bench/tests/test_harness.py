"""The harness: no chip, no result; a run's last line has exactly the
contract's keys; a tiny cell served on the CPU comes out correct."""
from __future__ import annotations

import json
import subprocess
import sys

import pytest

from bench_cells import BENCH, CELL, ROOT, tiny_cell

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_refuses_a_cpu_backend():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(ROOT)}
    p = subprocess.run(
        [sys.executable, str(BENCH / "run_cell.py"), "--workload",
         CELL, "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_system_under_test(tmp_path):
    import shutil

    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload",
         CELL, "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_every_cell_loads_from_its_files():
    import run_cell

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run_cell.load_cell(w["name"])
        names = {m["name"] for m in cell["per_layer"]}
        for m in names:
            assert callable(run_cell.metric_reader(m))
        assert {"frame_err_p99", "hole_err_max"} <= set(cell["limits"])


@pytest.fixture(scope="module")
def tiny_result():
    import run_cell

    return run_cell.run(tiny_cell(), 2**31 + 11, 2.0, trace=False,
                        require_chip=False)


def test_last_line_has_the_contract_keys(tiny_result):
    line = json.loads(json.dumps(tiny_result))
    assert list(line) == KEYS
    import run_cell

    cell = run_cell.load_cell(tiny_cell()["name"])
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_tiny_cell_is_correct(tiny_result):
    assert tiny_result["correct"] is True
    assert tiny_result["attempted"] > 0 and tiny_result["failed"] == 0


def test_sample_mixes_first_and_later_windows():
    import run_cell

    recs = [{"in_window": True, "first": i % 3 == 0, "tick": i, "slot": 0}
            for i in range(9)]
    picked = run_cell.pick_windows(recs, 5, seed=4)
    assert len(picked) == 5
    assert sum(r["first"] for r in picked) == 3
    assert picked == run_cell.pick_windows(recs, 5, seed=4)
    assert run_cell.pick_windows(recs[:1], 5, seed=4) == recs[:1]
    firsts = [r for r in recs if r["first"]]
    assert run_cell.pick_windows(firsts, 2, seed=4) != []
    picked = run_cell.pick_windows(recs, 4, seed=4)
    assert sum(r["first"] for r in picked) == 2
