"""Tiny cells for the benchmark's CPU tests: every width rule of the real
cells, at a size a test run holds (Pallas in interpret mode)."""
from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

CELL = "cicero-dvgo-baked.preview"
SHRINK = dict(grid_res=16, res=16, num_samples=16, pool_bucket=256,
              ray_chunk=256)


def tiny_cell(name: str = CELL, config_file: str = None) -> dict:
    """The benchmark's cell ``CELL`` serving the mix that ends ``name``
    (``cicero-dvgo-baked.<mix>``, ``bench/traffic/<mix>.json``), under
    the cell's limits, shrunk to a 16^3 grid, 16x16 frames and 16 samples
    per ray. The steady and churn mixes, whose sessions span many
    windows, have no cell on the chip (PERF.md, Open questions); here, at
    the CPU's float32, they drive the co-rendered reference and the
    carried state. ``config_file`` (under ``bench/``) swaps in another
    configuration, such as the MLP-decoder one that no cell runs yet."""
    import run_cell
    import traffic

    cell = copy.deepcopy(run_cell.load_cell(CELL))
    cell["name"] = name
    cell["mix"] = traffic.load(name.rsplit(".", 1)[-1])
    if config_file is not None:
        cell["config"] = json.loads((BENCH / config_file).read_text())
    cell["config"].update(SHRINK)
    cell["mix"]["check_windows"] = 3
    return cell
