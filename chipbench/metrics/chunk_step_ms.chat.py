"""Median wall time of engine steps that carry a prefill chunk, ms."""

import readings as rd


def read(run):
    return rd.step_ms(run, ("chunk", "fused"))
