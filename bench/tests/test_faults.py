"""The harness drives a run with the timed path broken underneath, and
``correct`` comes out false for each fault a serving cell can have. The
chip check is skipped; the tiny cells (one per cell of every configuration
in ``BENCHMARK.json``, ``bench_cells.cells``) run on the CPU with the
cells' own limits."""
from __future__ import annotations

import pytest

from bench_cells import (BENCHMARK_CONFIGS, carries_state, cell_id, cells,
                         tiny_cell)


def alter_one_frame(engine, result):
    """A frame altered where it is produced: each session window's first
    frame."""
    return result._replace(frames=result.frames.at[:, 0].add(0.05))


def drop_half_the_batch(engine, result):
    """The second slot's frames left out."""
    return result._replace(frames=result.frames.at[1].set(0.0))


def state_unchanged():
    """The tick returns the reference recurrence it was given: every
    window warps a stale reference frame."""
    held = {}

    def fault(engine, result):
        prev = held.get("ref")
        held["ref"] = (result.next_rgb_ref, result.next_dep_ref)
        if prev is None:
            return result
        engine._rgb_ref, engine._dep_ref = prev
        held["ref"] = prev
        return result._replace(next_rgb_ref=prev[0], next_dep_ref=prev[1])

    return fault


FAULTS = {"answer_altered": lambda: alter_one_frame,
          "half_the_batch": lambda: drop_half_the_batch,
          "state_unchanged": state_unchanged}


def plant_hole_fault(monkeypatch):
    """Planted in the program: the second slot's hole fills, as the fused
    tick scatters them back to their pixels, are off by 0.05. Every
    other pixel, and the first slot, are untouched."""
    from repro.core import raybatch

    scatter = raybatch.scatter_segments

    def faulty(values, addr, valid, size):
        second = (addr >= size // 2)[:, None]
        return scatter(values + 0.05 * second, addr, valid, size)

    monkeypatch.setattr(raybatch, "scatter_segments", faulty)


CELLS = cells(BENCHMARK_CONFIGS)


# one-window sessions carry no state from one tick to the next
CASES = [(cell, fault) for fault in sorted(FAULTS) for cell in CELLS
         if fault != "state_unchanged" or carries_state(*cell)]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{cell_id(*c)}-{f}" for c, f in CASES])
def test_fault_is_not_correct(cell, fault):
    import run_cell

    c = tiny_cell(*cell)
    c["mix"]["check_windows"] = 6
    r = run_cell.run(c, 2**31 + 5, 2.0, trace=False,
                     require_chip=False, fault=FAULTS[fault]())
    assert r["correct"] is False, r["checks"]


@pytest.mark.parametrize("cell", CELLS, ids=[cell_id(*c) for c in CELLS])
def test_one_slots_hole_rays_altered_is_not_correct(cell, monkeypatch):
    import run_cell

    plant_hole_fault(monkeypatch)
    c = tiny_cell(*cell)
    c["mix"]["check_windows"] = 6
    # fast enough that 16-pixel frames warped from a reference two frames
    # away have holes (at the cells' 192 pixels 2% of pixels are holes)
    c["mix"]["motion"]["step_deg"] = [4.0, 8.0]
    r = run_cell.run(c, 2**31 + 5, 2.0, trace=False, require_chip=False)
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["hole_err_max"]["value"] \
        > r["checks"]["hole_err_max"]["limit"]
