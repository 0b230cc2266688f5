"""The routed expert matmuls' least time over the grouped-matmul kernel's device time, %."""

import readings as rd


def read(run):
    return rd.gmm_roofline(run)
