"""Row 1 of the shared record's RIT counters: 5 + 5 spilled of 40 + 40."""


def expected(run, trace):
    return 100.0 * 10 / 80
