"""The serving loop the benchmark drives, and what it records.

The loop keeps ``serve_continuous``'s order: lifecycle sweep and retire,
begin a live batch if there is none, admit, retire, step (dropping the
live batch when nothing is runnable), retire. It adds only "submit the
requests now due" at the top, and it sleeps until the next due time when
nothing is queued or live. Each phase runs inside a host span
(``jax.profiler.TraceAnnotation``) named ``bench.<phase>``, so a device
trace can say what the host was doing in each idle gap.

Every time is ``time.perf_counter()`` seconds. A token becomes visible
when the step that sampled it returns (the engine syncs on the sampled
tokens before ``step`` returns).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from adapter import Adapter, NextStep


@dataclasses.dataclass
class ReqRecord:
    uid: int
    due: float
    prompt: np.ndarray
    padded: int  # padded prompt length (the engine's bucket)
    max_new: int
    first_chunk: Optional[float] = None  # first prefill chunk dispatched
    token_times: List[float] = dataclasses.field(default_factory=list)
    chunks: List[tuple] = dataclasses.field(default_factory=list)  # (t, real tokens)
    tokens: Optional[List[int]] = None  # served tokens, once retired
    status: Optional[str] = None


@dataclasses.dataclass
class StepRecord:
    start: float
    end: float
    step: NextStep
    chunk_real: int  # prompt tokens (not padding) in the chunk
    traced: bool  # ran while the profiler was on


class Recorder:
    def __init__(self, bucket: int):
        self.bucket = bucket
        self.reqs: Dict[int, ReqRecord] = {}
        self.steps: List[StepRecord] = []
        self._seen: Dict[int, int] = {}  # uid -> tokens recorded so far
        self.tracing = False

    def submitted(self, uid: int, due: float, prompt: np.ndarray, max_new: int):
        padded = self.bucket * max(1, -(-len(prompt) // self.bucket))
        self.reqs[uid] = ReqRecord(uid, due, prompt, padded, max_new)

    def stepped(self, start: float, end: float, nxt: NextStep, rows) -> None:
        real = 0
        if nxt.chunk_uid is not None:
            r = self.reqs[nxt.chunk_uid]
            pad = r.padded - len(r.prompt)
            lo, hi = nxt.chunk_start, nxt.chunk_start + nxt.chunk_len
            real = max(0, hi - max(lo, pad))
            if r.first_chunk is None:
                r.first_chunk = start
            r.chunks.append((end, real))
        self.steps.append(StepRecord(start, end, nxt, real, self.tracing))
        for uid, n_tok in rows:
            self.reqs[uid].token_times.extend([end] * (n_tok - self._seen.get(uid, 0)))
            self._seen[uid] = n_tok

    def retired(self, completions) -> None:
        for c in completions:
            r = self.reqs[c.uid]
            r.tokens, r.status = list(c.tokens), c.status
            self._seen.pop(c.uid, None)


class Loop:
    """One engine, driven through the adapter, one ``iterate`` at a time."""

    def __init__(self, engine, rec: Recorder, sampling, key):
        import jax

        self.jax = jax
        self.engine = engine
        self.ad = Adapter(engine)
        self.rec = rec
        self.sampling = sampling
        self.key = key
        self.pending: List = []  # (due time, Request) not yet submitted, by due time

    def submit_due(self, now: float) -> None:
        with TraceAnnotation("bench.submit"):
            while self.pending and self.pending[0][0] <= now:
                due, req = self.pending.pop(0)
                uid = self.engine.submit(req)
                self.rec.submitted(uid, due, np.asarray(req.prompt), req.max_new_tokens)

    def iterate(self, deadline: float) -> bool:
        """One pass of the loop; False when there was nothing to do (it
        then slept until the next due time or ``deadline``)."""
        e, ad, rec = self.engine, self.ad, self.rec
        self.submit_due(time.perf_counter())
        if not len(e.scheduler) and not ad.live():
            with TraceAnnotation("bench.idle"):
                wake = self.pending[0][0] if self.pending else deadline
                time.sleep(max(0.0, min(wake, deadline) - time.perf_counter()))
            return False
        with TraceAnnotation("bench.retire"):
            ad.reap()
            rec.retired(e.retire())
        if not ad.live():
            with TraceAnnotation("bench.begin"):
                ad.begin()
        with TraceAnnotation("bench.admit"):
            e.admit(self.sampling)
            rec.retired(e.retire())
        self.key, sub = self.jax.random.split(self.key)
        nxt = ad.next_step()
        if nxt is None:
            with TraceAnnotation("bench.drop"):
                e.step(self.sampling, sub)  # finds nothing runnable
                ad.drop()
            return True
        with TraceAnnotation(f"bench.step.{nxt.kind}"):
            t0 = time.perf_counter()
            e.step(self.sampling, sub)
            t1 = time.perf_counter()
        rec.stepped(t0, t1, nxt, ad.rows())
        with TraceAnnotation("bench.retire"):
            rec.retired(e.retire())
        return True
