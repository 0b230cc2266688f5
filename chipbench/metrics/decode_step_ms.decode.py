"""Median wall time of decode-only engine steps, ms."""

import readings as rd


def read(run):
    return rd.step_ms(run, ("decode",))
