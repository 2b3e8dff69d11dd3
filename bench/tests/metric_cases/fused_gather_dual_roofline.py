"""The gather work of the shared record's ticks at the roofline, over
the 0.25 s of ``fused_gather_dual`` in the shared trace."""
import peaks


def expected(run, trace):
    need = sum(peaks.roofline_s(t["gather_work"]["flops"],
                                t["gather_work"]["bytes"], run["peaks"])[0]
               for t in run["ticks"])
    return 100.0 * need / 0.25


NOTHING = {"empty_trace": lambda run, trace, empty: (run, empty)}
