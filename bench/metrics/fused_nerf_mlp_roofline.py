"""Kernels: the fused MLP decoder's share of its roofline, in percent:
the least time the chip could take to decode every live hole and
reference sample of the window's ticks (the architecture's
``decoder_work``) over the kernel's device time in the trace. Nothing to
read with a direct decoder."""
import peaks


def read(run, trace):
    t = (trace or {}).get("ops", {}).get("fused_nerf_mlp")
    if not t or run["config"]["decoder"] != "mlp":
        return None
    need = sum(peaks.roofline_s(k["mlp_work"]["flops"],
                                k["mlp_work"]["bytes"], run["peaks"])[0]
               for k in run["ticks"])
    return 100.0 * need / t
