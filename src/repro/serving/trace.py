"""Program spans and per-request records (DESIGN.md §4g).

Two records, both on ``time.perf_counter()`` seconds:

- **Spans**, on only while the JAX profiler records. ``Recorder.span``
  checks the profiler once; while it runs, each span is also a
  ``jax.profiler.TraceAnnotation`` of the same name (so it shows beside
  the device ops in the xplane) and lands in a bounded ring as
  ``Span(name, start, end, parent, uid, attrs)``. With the profiler off a
  span is that one check and a shared no-op object.
- **Request records**, always on: a few clock reads per request
  (submitted, joined, first chunk, first token, finished), its status,
  chunk and preemption counts. Finished records beyond ``FINISHED_KEPT``
  are dropped, oldest first.

``current()`` is the most recent engine's recorder, for in-process
readers (a benchmark, an operator's debug hook) that do not hold the
engine.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Deque, Dict, List, NamedTuple, Optional

from jax._src.lib import _profiler  # private: the profiler's own on/off flag
from jax.profiler import TraceAnnotation

# True while a profiler session records (~0.1 us a call)
profiling = _profiler.TraceMe.is_enabled

SPANS_KEPT = 1 << 17  # ~13 spans a step: thousands of steps
FINISHED_KEPT = 8192  # finished request records kept, newest


class Span(NamedTuple):
    name: str
    start: float
    end: float
    parent: Optional[int]  # uid of the enclosing span, None at the top
    uid: int  # this span's id, unique within its recorder
    attrs: Dict[str, Any]


@dataclasses.dataclass
class RequestRecord:
    uid: int
    submitted: float
    joined: Optional[float] = None  # first admission
    first_chunk: Optional[float] = None  # start of the step carrying its first chunk
    first_token: Optional[float] = None  # its first token is on the host
    finished: Optional[float] = None  # retired (any status)
    status: str = "queued"  # queued | live | ok | cancelled | deadline
    chunks: int = 0  # prefill chunks run, a re-admission's included
    preemptions: int = 0


class _Off:
    """The span while the profiler is off: falsy, and its clock reads
    are read now."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def started(self) -> float:
        return time.perf_counter()

    ended = started


_OFF = _Off()


class _On:
    __slots__ = ("rec", "name", "attrs", "start", "end", "uid", "parent", "_ann")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict[str, Any]):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __bool__(self) -> bool:
        return True

    def __enter__(self):
        rec = self.rec
        rec._next += 1
        self.uid = rec._next
        self.parent = rec._open[-1] if rec._open else None
        rec._open.append(self.uid)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        self.rec._open.pop()
        self.rec.spans.append(
            Span(self.name, self.start, self.end, self.parent, self.uid, self.attrs)
        )

    def started(self) -> float:
        return self.start

    def ended(self) -> float:
        return self.end


class Recorder:
    """One engine's spans and request records (its serving thread's:
    spans nest by the order they open)."""

    def __init__(self):
        self.spans: Deque[Span] = collections.deque(maxlen=SPANS_KEPT)
        self.requests: Dict[int, RequestRecord] = {}
        self._finished: Deque[int] = collections.deque()
        self._open: List[int] = []
        self._next = 0

    def span(self, name: str, **attrs):
        """Context manager timing ``name`` while the profiler records.
        The returned object is falsy when off; ``started()``/``ended()``
        give its clock reads (read on the spot when off), so bookkeeping
        that times the same work shares them."""
        if not profiling():
            return _OFF
        return _On(self, name, attrs)

    # -- request records -----------------------------------------------------
    def submitted(self, uid: int) -> None:
        self.requests[uid] = RequestRecord(uid, time.perf_counter())

    def get(self, uid: int) -> Optional[RequestRecord]:
        return self.requests.get(uid)

    def finished(self, uid: int, status: str) -> Optional[RequestRecord]:
        r = self.requests.get(uid)
        if r is None or r.finished is not None:
            return r
        r.finished, r.status = time.perf_counter(), status
        self._finished.append(uid)
        while len(self._finished) > FINISHED_KEPT:
            self.requests.pop(self._finished.popleft(), None)
        return r


_current: Optional[Recorder] = None


def install(rec: Optional[Recorder]) -> Optional[Recorder]:
    """Make ``rec`` the one ``current()`` returns (each new engine does)."""
    global _current
    _current = rec
    return rec


def current() -> Optional[Recorder]:
    """The most recent engine's recorder (None before any engine)."""
    return _current
