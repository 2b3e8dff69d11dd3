"""Device: share of the window in which no operation ran on the chip,
from the device trace, in percent."""


def read(run, trace):
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
