"""Model FLOPs of the traced steps over their wall time at the chip's peak,
%. The shared count takes every layer's FFN as the MoE layer's, the dense
first layer's too (138.7 against its 134.5 MFLOP a token)."""

import readings as rd


def read(run):
    return rd.mfu(run)
