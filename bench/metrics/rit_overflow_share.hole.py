"""MVoxel streaming: share of the hole stage's live gather samples that
spilled past their (segment, MVoxel) RIT bucket and took the XLA fallback
gather, from the engine's per-tick RIT counters, in percent."""


def read(run, trace):
    spilled = sum(t["rit"][0][0] for t in run["ticks"])
    total = sum(t["rit"][0][1] for t in run["ticks"])
    return 100.0 * spilled / total if total else None
