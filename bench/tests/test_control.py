"""The control: the plain reference computed at three bfloat16 passes
(the precision below the float32 the configurations state) and put in
the program's place, against the float32 reference. ``bench/control.py``
reads it on the chip at the cell's size, where it comes out not correct
by the cell's own limits (``frame_err_p99`` about 6x its limit,
``hole_err_max`` about 3x). At this tiny size it reads less, but still
fails them, and reads far above the program; its verdict is the
harness's own judgement of its readings. One case per tiny cell of every
configuration in ``BENCHMARK.json`` and of the MLP decoder's."""
from __future__ import annotations

import pytest

from bench_cells import CONFIG_FILES, cell_id, cells, tiny_cell

CELLS = cells(list(CONFIG_FILES))


@pytest.mark.parametrize("config,mix", CELLS,
                         ids=[cell_id(*c) for c in CELLS])
def test_control_fails_where_the_program_passes(config, mix):
    import run_cell

    c = tiny_cell(config, mix)
    r = run_cell.run(c, 2**31 + 3, 2.0, trace=False, require_chip=False,
                     control="high")
    program, control = r["readings"], r["control"]
    assert r["correct"] is True
    for name in ("frame_err_p99", "hole_err_max", "ref_rgb_err",
                 "ref_depth_err"):
        if program["settled_hole_pixels"] or name != "hole_err_max":
            assert control[name] >= 10 * max(program[name], 1e-9), name
    # the control is judged by the cell's own limits, as the program is,
    # and comes out not correct
    assert set(r["control_checks"]) == set(r["checks"])
    assert r["control_correct"] is run_cell.passed(r["control_checks"],
                                                   control)
    assert r["control_correct"] is False, r["control_checks"]
