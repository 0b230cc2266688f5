"""Host time of a chunk-carrying step, median, ms: the program's traced
``engine.step`` spans of kind chunk or fused less their ``engine.sync``
child."""

import program_records as pr


def read(run):
    return pr.step_host_ms(run, ("chunk", "fused"))
