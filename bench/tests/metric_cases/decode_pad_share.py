"""The counters' decode row (padding rows, rows decoded), which the shared
record lacks, added to its two ticks after a pad row: 30 + 10 padding
rows of 100 + 60, summed over the ticks; nothing from a program whose
counters stop at the pad row."""

ROWS = ([30, 100], [10, 60])


def extend(run):
    return dict(run, ticks=[dict(t, rit=t["rit"] + [[0, 1], row])
                            for t, row in zip(run["ticks"], ROWS)])


def expected(run, trace):
    return 100.0 * 40 / 160


NOTHING = {"no_decode_row": lambda run, trace, empty: (
    dict(run, ticks=[dict(t, rit=t["rit"][:3]) for t in run["ticks"]]),
    trace)}
