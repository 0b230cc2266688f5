"""Reduction of a profiler trace to device ops and host spans.

``load`` reads the ``.xplane.pb`` a ``jax.profiler`` session wrote and
keeps two lists of ``Event(name, start, end)`` in seconds on the
profiler's clock: the device's operations (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) and the harness's host spans (events named
``bench.*``). Everything else here is arithmetic on those lists, tested
on a small recorded trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


def short(name: str) -> str:
    """An HLO op's name, without the text the TPU trace gives after it
    (``%grouped_matmul.44 = bf16[...] custom-call(...)`` ->
    ``grouped_matmul.44``)."""
    return name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]  # device index -> its operations, by start
    spans: List[Event]  # harness host spans, by start

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)


def _device_index(plane_name: str) -> Optional[int]:
    if not plane_name.startswith(DEVICE_PLANE):
        return None
    tail = plane_name[len(DEVICE_PLANE):]
    return int(tail) if tail.isdigit() else None


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == OPS_LINE:
                ops.setdefault(dev, []).extend(
                    Event(short(e.name), e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
            elif dev is None:
                spans.extend(
                    Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    for v in ops.values():
        v.sort(key=lambda e: e.start)
    spans.sort(key=lambda e: e.start)
    return Trace(ops, spans)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Tuple[float, float]], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by the merged intervals."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged if e > lo and s < hi)


def busy(trace: Trace, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which some operation ran, averaged
    over the devices."""
    if not trace.ops:
        return 0.0
    return sum(covered(union((e.start, e.end) for e in ops), lo, hi)
               for ops in trace.ops.values()) / len(trace.ops)


def gaps(trace: Trace, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals of ``[lo, hi]`` in which device 0 of the trace ran nothing."""
    merged = union((e.start, e.end) for e in trace.ops[trace.devices[0]])
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def span_at(spans: Sequence[Event], t: float) -> str:
    """The innermost harness span open at ``t`` ("none" outside all)."""
    best = None
    for s in spans:
        if s.start <= t < s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name if best is not None else "none"


def op_time_in(trace: Trace, match, windows: Sequence[Tuple[float, float]]) -> float:
    """Device seconds of operations whose name satisfies ``match`` and that
    start inside one of ``windows`` (sorted, not overlapping), summed over
    devices and averaged."""
    if not trace.ops:
        return 0.0
    import bisect

    starts = [w[0] for w in windows]
    total = 0.0
    for ops in trace.ops.values():
        for e in ops:
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.start < windows[i][1] and match(e.name):
                total += e.dur
    return total / len(trace.ops)


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The operations that contain no other: the TPU trace nests the
    ops of a loop body inside the loop's own event (``while.6``)."""
    out = []
    for i, e in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt.start >= e.end or nxt.end > e.end:
            out.append(e)
    return out


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> List[List]:
    """The ``n`` operation names that took most device time in ``[lo, hi]``,
    averaged over devices; loops are counted by their body's ops."""
    by: Dict[str, float] = defaultdict(float)
    for ops in trace.ops.values():
        for e in leaves(ops):
            if lo <= e.start < hi:
                by[e.name] += e.dur / len(trace.ops)
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps of device 0, each named by the harness
    span open at its midpoint."""
    g = sorted(gaps(trace, lo, hi), key=lambda ab: ab[0] - ab[1])[:n]
    return [[span_at(trace.spans, (a + b) / 2), b - a] for a, b in g]


def idle_by_span(trace: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Idle seconds of device 0 in ``[lo, hi]``, by the harness span open
    at each gap's midpoint."""
    out: Dict[str, float] = defaultdict(float)
    for a, b in gaps(trace, lo, hi):
        out[span_at(trace.spans, (a + b) / 2)] += b - a
    return dict(out)
