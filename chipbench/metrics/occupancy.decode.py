"""Rows decoding per decode-carrying step over the slots, %."""

import readings as rd


def read(run):
    v = rd.occupancy(run)
    return None if v is None else 100.0 * v
