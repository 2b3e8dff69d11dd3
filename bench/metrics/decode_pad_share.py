"""Decoder: share of the rows the fused ticks' decoder ran on that were
pooled padding (hole-bucket rows past a session's hole total), over the
window's ticks, from the engine's per-tick counters (row 3: padding rows,
rows decoded), in percent. A program whose counters have no such row
reads nothing."""


def read(run, trace):
    rows = [t["rit"][3] for t in run["ticks"] if len(t["rit"]) > 3]
    pad = sum(r[0] for r in rows)
    total = sum(r[1] for r in rows)
    return 100.0 * pad / total if total else None
