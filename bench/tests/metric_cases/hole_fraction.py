"""The mean of the shared record's hole fractions, 1% and 3%."""


def expected(run, trace):
    return 2.0
