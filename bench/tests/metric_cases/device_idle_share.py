"""9 s busy in the shared trace's 10 s window."""


def expected(run, trace):
    return 10.0
