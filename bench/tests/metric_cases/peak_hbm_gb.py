"""The shared record's 1.5e9 peak bytes."""


def expected(run, trace):
    return 1.5
