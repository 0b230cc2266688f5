"""Put a traced run's device-idle time and device ops down to the program.

    python3 chipbench/program_split.py --workload <cell> --seed <n> --seconds <s>

Runs one cell as ``run.py --trace 1`` does, prints its lines, then one
more JSON line, ``{"program_split": ...}`` (only ``ops``, and exit 1,
where the program records nothing):

- ``idle``: the device-idle seconds inside the harness's traced steps by
  the innermost program span open over them (``program_records``), and
  the share that falls inside a named child of ``engine.step``;
- ``waits``: per request due in the window, the harness's first chunk
  less its due time against the program's first chunk less its
  submission: the largest difference beside the run's longest step, and
  the two program waits' 95th percentiles;
- ``ops``: the device ops that took most time in the trace, each with
  the XLA module (``jit_decode``, ``jit_chunk``, ``jit_fused``, ...) it
  ran in, and the stat names the TPU plane's op events carry (the model
  scopes are in the compiled HLO's op metadata, not in these events),
  with the distinct ``Time Scale Multiplier`` values and the ratio of
  the ops' ``device_duration_ps`` to their trace durations;
- ``span_cost_us``: one span's cost with the profiler off and on (a loop
  of ``Recorder.span`` calls on this host), the spans a traced step
  records, and the traced ``engine.step`` spans' self time (the step
  less its children: an upper bound on what the spans cost a step).

A diagnostic beside the benchmark: it changes nothing ``run.py`` measures.
"""

from __future__ import annotations

import argparse
import bisect
import json
import statistics
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

import run as bench  # first: its clock starts at import, as a run's does

import devtrace
import program_records as pr
import readings as rd

MODULES_LINE = "XLA Modules"  # the device plane's line of whole-program events
STEP_CHILDREN = ("engine.blocks", "engine.inputs", "engine.dispatch", "engine.sample",
                 "engine.sync", "engine.book")


def device_ops(path: str, n: int = 40, plane_prefix: str = devtrace.DEVICE_PLANE,
               ops_line: str = devtrace.OPS_LINE) -> dict:
    """The ``n`` leaf device ops that took most time, by the XLA module
    (program) each ran in and op name: two programs can each have a
    ``paged_attention.7``. An op's module is the ``XLA Modules`` event
    open at its start, its name without the run id in parentheses."""
    from jax.profiler import ProfileData

    total = defaultdict(float)
    stat_names = set()
    scales = set()
    clock = [0.0, 0.0]  # the kept ops' device_duration_ps and duration_ns, summed
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(plane_prefix):
            continue
        lines = {line.name: sorted(line.events, key=lambda e: e.start_ns)
                 for line in plane.lines}
        mods = lines.get(MODULES_LINE, [])
        starts = [m.start_ns for m in mods]
        for name, events in lines.items():
            if not name.startswith(ops_line):
                continue
            kept = devtrace.leaves([devtrace.Event(i, e.start_ns, e.start_ns + e.duration_ns)
                                    for i, e in enumerate(events)])
            for k in kept:
                e = events[k.name]
                stats = dict(e.stats)
                stat_names.update(stats)
                if "Time Scale Multiplier" in stats:
                    scales.add(round(float(stats["Time Scale Multiplier"]), 9))
                if "device_duration_ps" in stats:
                    clock[0] += float(stats["device_duration_ps"])
                    clock[1] += e.duration_ns
                i = bisect.bisect_right(starts, e.start_ns) - 1
                inside = i >= 0 and e.start_ns < mods[i].start_ns + mods[i].duration_ns
                module = mods[i].name.split("(")[0] if inside else "-"
                total[(module, devtrace.short(e.name))] += e.duration_ns * 1e-9
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return {"stat_names": sorted(stat_names), "time_scales": sorted(scales)[:8],
            "device_ps_per_trace_ns": clock[0] / clock[1] if clock[1] else None,
            "top": [{"module": m, "op": k, "s": round(v, 6)} for (m, k), v in top]}


def idle(run, prog) -> dict:
    by = pr.idle_by_span(run, prog)
    total = sum(by.values())
    named = sum(v for k, v in by.items() if k in STEP_CHILDREN)
    return {"seconds": {k: round(v, 6) for k, v in sorted(by.items(), key=lambda kv: -kv[1])},
            "total_s": round(total, 6), "in_named_children": named / total if total else None}


def waits(run, prog) -> dict:
    diffs = []
    for r in rd.due_in_window(run):
        p = prog.get(r.uid)
        if p is None or r.first_chunk is None or p.first_chunk is None:
            continue
        diffs.append((r.first_chunk - r.due) - (p.first_chunk - p.submitted))
    longest = max((s.end - s.start for s in run.steps()), default=None)
    return {"requests": len(diffs),
            "diff_ms": [rd.ms(min(diffs, default=None)), rd.ms(max(diffs, default=None))],
            "longest_step_ms": rd.ms(longest),
            "within": sum(abs(d) <= longest for d in diffs) if longest else 0,
            "admit_wait_p95_ms": rd.ms(rd.pctl(pr.admit_waits(run, prog), 95)),
            "prefill_wait_p95_ms": rd.ms(rd.pctl(pr.prefill_waits(run, prog), 95)),
            "queue_wait_p95_ms": rd.ms(rd.pctl(rd.queue_waits(run), 95))}


def span_cost(run, prog, n: int = 20000) -> dict:
    import jax

    from repro.serving import trace

    def loop(rec):
        t = time.perf_counter()
        for _ in range(n):
            with rec.span("engine.step"):
                pass
        return (time.perf_counter() - t) / n * 1e6

    off = loop(trace.Recorder())
    d = tempfile.mkdtemp(prefix="span_cost_")
    jax.profiler.start_trace(d)
    try:
        on = loop(trace.Recorder())
    finally:
        jax.profiler.stop_trace()
    spans = pr.traced_spans(run, prog)
    steps = [s for s in spans if s.name == pr.STEP]
    kids = defaultdict(float)
    for s in spans:
        kids[s.parent] += s.end - s.start
    self_us = [(s.end - s.start - kids[s.uid]) * 1e6 for s in steps]
    t = time.perf_counter()
    for _ in range(n):
        time.perf_counter()
    return {"off_us_per_span": off, "on_us_per_span": on,
            "perf_counter_us": (time.perf_counter() - t) / n * 1e6,
            "spans_per_step": len(spans) / len(steps) if steps else None,
            "step_self_us_median": statistics.median(self_us) if self_us else None}


def main(argv=None, require_tpu: bool = True, root: Path = bench.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    got = {}
    load = devtrace.load

    class Run(rd.Run):  # the run's records, kept past run.py's use of them
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            got["run"] = self

    def capture_load(path):
        try:
            got["ops"] = device_ops(path)
        except Exception as e:  # the run goes on: its line is the benchmark's
            got["ops"] = {"error": repr(e)}
        return load(path)

    rd.Run, devtrace.load = Run, capture_load
    rc = bench.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"],
                    require_tpu=require_tpu, root=root)
    if rc or "run" not in got:
        return rc or 1
    run, prog = got["run"], pr.recorder()
    out = {"cell": args.workload, "seed": args.seed, "ops": got.get("ops")}
    if prog is not None:
        out.update(idle=idle(run, prog), waits=waits(run, prog),
                   span_cost_us=span_cost(run, prog))
    print(json.dumps({"program_split": out}), flush=True)
    if prog is None:
        print("program_split: the program records nothing", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
