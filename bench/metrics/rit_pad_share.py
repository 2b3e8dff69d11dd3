"""MVoxel streaming: share of the columns in the live blocks of the
ragged RIT that are padding (an MVoxel's run rounded up to whole blocks),
over the window's fused ticks, from the engine's per-tick RIT counters
(row 2: pad columns, columns), in percent. A program whose counters have
no such row reads nothing."""


def read(run, trace):
    rows = [t["rit"][2] for t in run["ticks"] if len(t["rit"]) > 2]
    pad = sum(r[0] for r in rows)
    total = sum(r[1] for r in rows)
    return 100.0 * pad / total if total else None
