"""Queue wait, 95th percentile, ms: due time to the first prefill chunk
dispatched, over the requests due in the window (one not started by the
close counts at its age)."""

import readings as rd


def read(run):
    return rd.ms(rd.pctl(rd.queue_waits(run), 95))
