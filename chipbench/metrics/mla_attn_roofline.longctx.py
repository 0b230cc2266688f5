"""Latent attention's least time over its decode kernel's device time in
decode-only steps, %: the latent rows at the live positions, 1,152 bytes
a position and layer, over the chip's HBM bandwidth (or the absorbed
scores and sums over its peak, whichever is longer), against the device
time of the ``mla_decode_attention`` Pallas kernel inside those steps."""

import readings as rd
import workcount as wc

KERNEL = "mla_decode_attention"  # the kernel's op name in the TPU trace


def read(run):
    steps = [s for s in run.traced_steps() if s.step.kind == "decode"]
    least = sum(wc.least_time(*wc.paged_attn_work(run.model, s.step.decode_ctx, run.arch),
                              run.peaks) for s in steps)
    return rd._kernel_share(run, KERNEL, steps, least)
