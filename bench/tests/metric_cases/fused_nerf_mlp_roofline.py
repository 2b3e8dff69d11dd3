"""The decoder work of the shared record's ticks at the roofline, over
the 0.125 s of ``fused_nerf_mlp`` in the shared trace; nothing with the
direct decoder, which runs no MLP."""
import peaks


def expected(run, trace):
    need = sum(peaks.roofline_s(t["mlp_work"]["flops"],
                                t["mlp_work"]["bytes"], run["peaks"])[0]
               for t in run["ticks"])
    return 100.0 * need / 0.125


NOTHING = {
    "empty_trace": lambda run, trace, empty: (run, empty),
    "direct_decoder": lambda run, trace, empty: (
        dict(run, config=dict(run["config"], decoder="direct")), trace),
}
