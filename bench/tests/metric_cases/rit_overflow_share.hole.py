"""Row 0 of the shared record's RIT counters: 3 + 3 spilled of 10 + 10."""


def expected(run, trace):
    return 100.0 * 6 / 20
