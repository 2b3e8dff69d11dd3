"""The 95th percentile of the shared record's 21 queue waits, 0 to 20 s."""


def expected(run, trace):
    return 19.0
