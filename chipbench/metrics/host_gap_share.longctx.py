"""Share of the traced steps' wall time in which no device op runs, %."""

import readings as rd


def read(run):
    return rd.host_gap_share(run)
