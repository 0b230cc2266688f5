"""Model FLOPs of the traced steps over their wall time at the chip's peak, %."""

import readings as rd


def read(run):
    return rd.mfu(run)
