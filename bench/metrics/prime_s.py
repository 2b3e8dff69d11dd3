"""Tick programs: device seconds per run of the admission prime
(``_prime_select``), from the device trace of the window."""


def read(run, trace):
    m = (trace or {}).get("modules", {}).get("_prime_select")
    return m["seconds"] / m["count"] if m and m["count"] else None
