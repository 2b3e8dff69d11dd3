"""0.5 s over 1 run of ``_prime_select`` in the shared trace."""


def expected(run, trace):
    return 0.5


NOTHING = {"empty_trace": lambda run, trace, empty: (run, empty)}
