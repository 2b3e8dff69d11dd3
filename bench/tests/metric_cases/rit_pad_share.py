"""The ragged RIT's third counter row (pad columns, columns), which the
shared record lacks, added to its two ticks: 2 + 6 pad columns of 16 +
48, summed over the ticks; nothing from the spill rows alone."""

ROWS = ([2, 16], [6, 48])


def extend(run):
    return dict(run, ticks=[dict(t, rit=t["rit"] + [row])
                            for t, row in zip(run["ticks"], ROWS)])


def expected(run, trace):
    return 100.0 * 8 / 64


NOTHING = {"spill_rows_only": lambda run, trace, empty: (
    dict(run, ticks=[dict(t, rit=t["rit"][:2]) for t in run["ticks"]]),
    trace)}
