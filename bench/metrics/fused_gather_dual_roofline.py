"""Kernels: the fused dual MVoxel gather's share of its roofline, in
percent: the least time the chip could take for the gather the window's
ticks require (the architecture's ``gather_work``: every live hole and
reference sample, one table sweep per tick) over the kernel's device time
in the trace."""
import peaks


def read(run, trace):
    t = (trace or {}).get("ops", {}).get("fused_gather_dual")
    if not t:
        return None
    need = sum(peaks.roofline_s(k["gather_work"]["flops"],
                                k["gather_work"]["bytes"], run["peaks"])[0]
               for k in run["ticks"])
    return 100.0 * need / t
