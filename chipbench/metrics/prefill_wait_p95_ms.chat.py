"""Prefill line wait, 95th percentile, ms, as the program records it:
first admission to the start of the step that carries the first prompt
chunk, over the requests due in the window and admitted by its close
(one whose first chunk has not run by the close counts to it)."""

import program_records as pr
import readings as rd


def read(run):
    return rd.ms(rd.pctl(pr.prefill_waits(run), 95))
