"""Device: peak HBM in use over the run (the runtime's
``peak_bytes_in_use``, read before the reference runs), in GB."""


def read(run, trace):
    b = run.get("memory_peak_bytes")
    return b / 1e9 if b else None
