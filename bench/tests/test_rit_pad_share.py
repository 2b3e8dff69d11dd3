"""``rit_pad_share``'s reader on run records made by hand: the share of
pad columns over the ticks that carry the ragged RIT's padding row, and
nothing read from counters without it."""
from __future__ import annotations

import pytest


def _run(*rits):
    return {"ticks": [{"rit": r} for r in rits]}


@pytest.mark.parametrize("rits,expected", [
    # (pad columns, columns) summed over the ticks, not averaged per tick
    ([[[0, 10], [0, 40], [3, 12]], [[0, 20], [0, 40], [9, 36]]],
     100.0 * 12 / 48),
    ([[[0, 10], [0, 40], [0, 512]]], 0.0),
    # a tick without the row (a program before the ragged RIT) adds nothing
    ([[[0, 10], [0, 40], [5, 20]], [[1, 10], [2, 40]]], 25.0),
])
def test_reader_arithmetic(rits, expected):
    import run_cell

    value = run_cell.metric_reader("rit_pad_share")(_run(*rits), None)
    assert value == pytest.approx(expected, rel=1e-12)


@pytest.mark.parametrize("rits", [
    [[[3, 10], [5, 40]]],           # the spill counters alone
    [],                              # no tick in the window
    [[[0, 0], [0, 0], [0, 0]]],      # no column swept
])
def test_nothing_to_read_gives_nothing(rits):
    import run_cell

    assert run_cell.metric_reader("rit_pad_share")(_run(*rits), None) is None
