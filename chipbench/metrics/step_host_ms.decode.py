"""Host time of a decode step, median, ms: the program's traced
``engine.step`` spans of kind decode less their ``engine.sync`` child."""

import program_records as pr


def read(run):
    return pr.step_host_ms(run, ("decode",))
