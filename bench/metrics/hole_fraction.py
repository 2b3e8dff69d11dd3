"""SpaRW: mean share of the delivered frames' pixels that were holes (the
engine's per-frame hole counts), in percent."""
import numpy as np


def read(run, trace):
    h = run["hole_fractions"]
    return 100.0 * float(np.mean(h)) if h else None
