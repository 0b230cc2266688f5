"""What the program records of itself, read for per-layer metrics.

The engine keeps per-request event times and, while the profiler runs,
host spans inside its step (``repro.serving.trace``), all on
``time.perf_counter()`` seconds: the harness's own clock, so
``Run.window``, ``Run.traced`` and ``Run.offset`` apply to them as they
are. Where the program has no such module or no engine recorder, every
function here finds nothing and its metric is left out of the line.
"""

from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import devtrace as tr
import readings as rd

STEP = "engine.step"
SYNC = "engine.sync"


def recorder():
    """The most recent engine's recorder, or None."""
    try:
        from repro.serving import trace
    except ImportError:
        return None
    return trace.current()


def _records(run: rd.Run, prog) -> List[Tuple]:
    """(harness record, program record) of each request due in the window."""
    prog = prog if prog is not None else recorder()
    if prog is None:
        return []
    out = []
    for r in rd.due_in_window(run):
        p = prog.get(r.uid)
        if p is not None:
            out.append((r, p))
    return out


def admit_waits(run: rd.Run, prog=None) -> List[float]:
    """Seconds from submission to first admission, per request due in the
    window; one not admitted by the close counts at its age."""
    hi = run.window[1]
    return [(p.joined if p.joined is not None and p.joined <= hi else hi) - p.submitted
            for _, p in _records(run, prog)]


def prefill_waits(run: rd.Run, prog=None) -> List[float]:
    """Seconds from first admission to the start of the step carrying the
    first prompt chunk, per request due in the window and admitted by the
    close; one whose first chunk has not run by the close counts to it."""
    hi = run.window[1]
    return [(p.first_chunk if p.first_chunk is not None and p.first_chunk <= hi else hi)
            - p.joined for _, p in _records(run, prog)
            if p.joined is not None and p.joined <= hi]


def traced_spans(run: rd.Run, prog=None) -> list:
    """The program's spans inside the traced seconds, by start."""
    prog = prog if prog is not None else recorder()
    if prog is None:
        return []
    lo, hi = run.traced
    return sorted((s for s in list(prog.spans) if lo <= s.start and s.end <= hi),
                  key=lambda s: s.start)


def step_host_ms(run: rd.Run, kinds: Sequence[str], prog=None) -> Optional[float]:
    """Median over traced ``engine.step`` spans of the given kinds of their
    duration less their ``engine.sync`` child (the host waiting on the
    device), ms."""
    spans = traced_spans(run, prog)
    sync: Dict[int, float] = defaultdict(float)
    for s in spans:
        if s.name == SYNC:
            sync[s.parent] += s.end - s.start
    host = [(s.end - s.start - sync[s.uid]) * 1e3 for s in spans
            if s.name == STEP and s.attrs.get("kind") in kinds]
    return statistics.median(host) if host else None


def idle_by_span(run: rd.Run, prog=None) -> Dict[str, float]:
    """Device-idle seconds inside the harness's traced steps, each piece
    put down to the innermost program span open over it on the trace's
    clock (``run.offset``): a child of ``engine.step`` by its name,
    ``engine.step`` itself outside its children, ``none`` outside it."""
    if run.trace is None or not run.trace.ops:
        return {}
    spans = traced_spans(run, prog)
    depth: Dict[int, int] = {}
    for s in spans:  # by start: a parent before its children
        depth[s.uid] = depth.get(s.parent, -1) + 1
    out: Dict[str, float] = defaultdict(float)
    for step in run.traced_steps():
        a, b = run.on_trace(step)
        for ga, gb in tr.gaps(run.trace, a, b):
            on = [s for s in spans
                  if s.start + run.offset < gb and s.end + run.offset > ga]
            cuts = sorted({ga, gb} | {min(max(t + run.offset, ga), gb)
                                      for s in on for t in (s.start, s.end)})
            for x, y in zip(cuts, cuts[1:]):
                m = (x + y) / 2
                open_ = [s for s in on if s.start + run.offset <= m < s.end + run.offset]
                name = max(open_, key=lambda s: depth[s.uid]).name if open_ else "none"
                out[name] += y - x
    return dict(out)
