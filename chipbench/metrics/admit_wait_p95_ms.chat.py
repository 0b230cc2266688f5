"""Admission wait, 95th percentile, ms, as the program records it:
submission to first admission (a free slot and its KV blocks), over the
requests due in the window (one not admitted by the close counts at its
age)."""

import program_records as pr
import readings as rd


def read(run):
    return rd.ms(rd.pctl(pr.admit_waits(run), 95))
