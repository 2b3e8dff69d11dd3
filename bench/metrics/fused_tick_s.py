"""Tick programs: device seconds per run of the fused serving tick
(``_tick_streaming``), from the device trace of the window."""


def read(run, trace):
    m = (trace or {}).get("modules", {}).get("_tick_streaming")
    return m["seconds"] / m["count"] if m and m["count"] else None
