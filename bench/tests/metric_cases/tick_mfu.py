"""The work of the shared record's ticks and admission primes at the
roofline (a prime that renders nothing counts nothing), over the 8 + 0.5
s of ``_tick_streaming`` and ``_prime_select`` in the shared trace."""
import peaks


def expected(run, trace):
    need = sum(peaks.roofline_s(w["flops"], w["bytes"], run["peaks"])[0]
               for t in run["ticks"] for w in (t["work"], t["prime_work"])
               if w["flops"])
    return 100.0 * need / 8.5


NOTHING = {"empty_trace": lambda run, trace, empty: (run, empty)}
