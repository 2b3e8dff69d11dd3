"""8 s over 2 runs of ``_tick_streaming`` in the shared trace."""


def expected(run, trace):
    return 4.0


NOTHING = {"empty_trace": lambda run, trace, empty: (run, empty)}
