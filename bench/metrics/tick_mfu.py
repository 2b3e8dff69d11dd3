"""Model step: the serving programs' share of the chip's peak, in
percent: the least time the chip could take for the work the window's
ticks and admission primes require (``work.tick_work``: per program run,
the larger of operations over peak FLOP/s and bytes over peak HBM
bandwidth) over the device time of those runs (``_tick_streaming`` and
``_prime_select`` in the trace)."""
import peaks

PROGRAMS = ("_tick_streaming", "_prime_select")


def read(run, trace):
    modules = (trace or {}).get("modules", {})
    spent = sum(modules[p]["seconds"] for p in PROGRAMS if p in modules)
    if not spent:
        return None
    need = 0.0
    for k in run["ticks"]:
        for w in (k["work"], k["prime_work"]):
            if w["flops"]:
                need += peaks.roofline_s(w["flops"], w["bytes"],
                                         run["peaks"])[0]
    return 100.0 * need / spent
