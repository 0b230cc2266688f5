"""Paged attention's least time over its kernel's device time in decode-only steps, %."""

import readings as rd


def read(run):
    return rd.paged_attn_roofline(run)
