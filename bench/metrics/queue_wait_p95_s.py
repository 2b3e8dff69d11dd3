"""Scheduler: 95th percentile of the queue wait (admission stamp minus
arrival stamp, both the engine's) of the sessions whose first window was
delivered in the window."""
import numpy as np


def read(run, trace):
    waits = run["queue_waits_s"]
    return float(np.percentile(waits, 95)) if waits else None
