"""Tiny cells for the benchmark's CPU tests: every width rule of the real
cells, at a size a test run holds (Pallas in interpret mode), each
configuration shrunk by its architecture's ``TINY``.

The cells come from ``BENCHMARK.json``: every cell there has a tiny cell
here, so a configuration that joins the benchmark is served, checked
against the reference, against the control and under planted faults by
the tests that are already here. ``CPU_ONLY`` adds the cells that have no
chip cell yet."""
from __future__ import annotations

import copy
import json
from pathlib import Path
from typing import List, Tuple

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())

CELL = "cicero-dvgo-baked.preview"
# Served only here, at the CPU's float32, under the limits of their
# configuration's chip cell (the preview cell's where it has none): the
# multi-window mixes, whose sessions drive the co-rendered reference and
# the carried state but which the program fails on the chip, and the MLP
# decoder's configuration, which no chip cell runs yet (PERF.md, Open
# questions).
CPU_ONLY = {"cicero-dvgo-baked": ("steady", "churn"),
            "cicero-dvgo": ("steady",)}
CONFIG_FILES = {"cicero-dvgo": "bench/configs/cicero-dvgo.json",
                **{c["name"]: c["file"] for c in BENCHMARK["configs"]}}
BENCHMARK_CONFIGS = [c["name"] for c in BENCHMARK["configs"]]


def cells(configs: List[str]) -> List[Tuple[str, str]]:
    """``(config, mix)`` of the tiny cells of ``configs``: each of their
    cells in ``BENCHMARK.json``, then the ``CPU_ONLY`` ones."""
    out = [(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]
           if w["config"] in configs]
    return out + [(c, m) for c in configs for m in CPU_ONLY.get(c, ())]


def cell_id(config: str, mix: str) -> str:
    return f"{config}.{mix}"


def carries_state(config: str, mix: str) -> bool:
    """Whether a session of the mix spans more than one window, so that a
    tick hands state (the co-rendered reference) to the next."""
    import traffic

    frames = traffic.load(mix)["session_frames"]
    window = json.loads((ROOT / CONFIG_FILES[config]).read_text())["window"]
    return not (frames["kind"] == "fixed" and frames["frames"] <= window)


def shrink(cell: dict) -> dict:
    """``cell`` at its architecture's ``TINY`` size, with 3 windows
    checked."""
    import run_cell

    cell["config"].update(run_cell.load_arch(cell["config"]).TINY)
    cell["mix"]["check_windows"] = 3
    return cell


def tiny_cell(config: str = "cicero-dvgo-baked", mix: str = "preview") -> dict:
    """The configuration ``config`` serving the mix ``mix``
    (``bench/traffic/<mix>.json``), under the limits of its cell in
    ``BENCHMARK.json`` (of its first cell, or ``CELL``'s, where it has
    none), shrunk by :func:`shrink`."""
    import run_cell
    import traffic

    chip = {(w["config"], w["traffic"]): w["name"]
            for w in BENCHMARK["workloads"]}
    base = chip.get((config, mix)) or next(
        (n for (c, _), n in chip.items() if c == config), CELL)
    cell = copy.deepcopy(run_cell.load_cell(base))
    cell["name"] = cell_id(config, mix)
    cell["mix"] = traffic.load(mix)
    cell["config"] = json.loads((ROOT / CONFIG_FILES[config]).read_text())
    return shrink(cell)
