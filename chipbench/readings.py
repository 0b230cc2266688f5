"""Metric arithmetic over a run's records and its reduced trace.

End-to-end metrics come from the harness's own clock over the whole
window; per-layer metrics from the ``--trace 1`` run, through the
readers in ``metrics/``, each a call into this file. A reader that finds
nothing to read returns None and the metric is left out of the line.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import devtrace as tr
import workcount as wc

# the kernels' op names in the TPU trace (the Pallas call takes the name
# of the jitted function that makes it), numbered: ``grouped_matmul.44``
GMM_KERNEL = "grouped_matmul"
PAGED_KERNEL = "paged_attention"


@dataclasses.dataclass
class Run:
    model: Dict[str, Any]
    traffic: Dict[str, Any]
    peaks: Dict[str, Any]
    rec: Any  # serving_loop.Recorder
    window: Tuple[float, float]  # perf_counter seconds
    due_until: float  # requests due before this were submitted in the window
    trace: Optional[tr.Trace] = None
    offset: float = 0.0  # trace clock minus perf_counter
    traced: Tuple[float, float] = (0.0, 0.0)  # perf_counter span of the trace

    def steps(self) -> List:
        lo, hi = self.window
        return [s for s in self.rec.steps if s.start >= lo and s.end <= hi]

    def traced_steps(self) -> List:
        lo, hi = self.traced
        return [s for s in self.rec.steps if s.traced and s.start >= lo and s.end <= hi]

    def on_trace(self, s) -> Tuple[float, float]:
        return s.start + self.offset, s.end + self.offset


def pctl(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear between closest ranks)."""
    return float(np.percentile(np.asarray(values, float), q)) if len(values) else None


def median(values: Sequence[float]) -> Optional[float]:
    return pctl(values, 50)


def ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else seconds * 1e3


# -- end to end ---------------------------------------------------------------
def due_in_window(run: Run) -> List:
    lo = run.window[0]
    return [r for r in run.rec.reqs.values() if lo <= r.due < run.due_until]


def ttfts(run: Run) -> List[float]:
    """Seconds from due time to the first token, for every request due in
    the window; one with no first token by the close counts at its age."""
    hi = run.window[1]
    return [(r.token_times[0] if r.token_times and r.token_times[0] <= hi else hi)
            - r.due for r in due_in_window(run)]


def tbts(run: Run) -> List[float]:
    """Every gap between consecutive output tokens of a request, both
    inside the window."""
    lo, hi = run.window
    out = []
    for r in run.rec.reqs.values():
        t = [x for x in r.token_times if lo <= x <= hi]
        out.extend(np.diff(t).tolist())
    return out


def output_tokens(run: Run) -> int:
    lo, hi = run.window
    return sum(sum(lo <= x <= hi for x in r.token_times) for r in run.rec.reqs.values())


def prompt_tokens(run: Run) -> int:
    lo, hi = run.window
    return sum(n for r in run.rec.reqs.values() for t, n in r.chunks if lo <= t <= hi)


# -- per layer: harness clock ------------------------------------------------------
def queue_waits(run: Run) -> List[float]:
    hi = run.window[1]
    return [(r.first_chunk if r.first_chunk is not None and r.first_chunk <= hi else hi)
            - r.due for r in due_in_window(run)]


def step_ms(run: Run, kinds: Sequence[str]) -> Optional[float]:
    d = [(s.end - s.start) * 1e3 for s in run.steps() if s.step.kind in kinds]
    return median(d)


def occupancy(run: Run) -> Optional[float]:
    """Mean rows decoding per decode-carrying step, over the slots."""
    n = [len(s.step.decode_ctx) for s in run.steps() if s.step.decode_ctx]
    return float(np.mean(n)) / run.traffic["slots"] if n else None


# -- per layer: trace -------------------------------------------------------------
def _flops(run: Run, s) -> float:
    st = s.step
    lo = hi = 0
    sampled = False
    if st.chunk_uid is not None:
        hi = st.chunk_start + st.chunk_len
        lo = hi - s.chunk_real  # the real tokens end the padded chunk
        # only a prompt's last chunk samples a token (never a fused one)
        sampled = st.kind == "chunk" and hi >= run.rec.reqs[st.chunk_uid].padded
    return wc.step_flops(run.model, (lo, hi), st.decode_ctx, sampled)


def mfu(run: Run) -> Optional[float]:
    """Model FLOPs of the traced steps over their wall time at peak, %."""
    steps = run.traced_steps()
    t = sum(s.end - s.start for s in steps)
    if not steps or t <= 0:
        return None
    return 100.0 * sum(_flops(run, s) for s in steps) / (t * run.peaks["flops_per_s"])


def host_gap_share(run: Run) -> Optional[float]:
    """Share of the traced steps' wall time with no device op running, %."""
    steps = run.traced_steps()
    if not steps or run.trace is None or not run.trace.ops:
        return None
    total = idle = 0.0
    for s in steps:
        a, b = run.on_trace(s)
        total += b - a
        idle += (b - a) - tr.busy(run.trace, a, b)
    return 100.0 * idle / total if total > 0 else None


def _kernel_share(run: Run, name: str, steps, least: float) -> Optional[float]:
    if not steps or run.trace is None or least <= 0:
        return None
    windows = [run.on_trace(s) for s in steps]
    dev = tr.op_time_in(run.trace, lambda n: n.split(".")[0] == name, windows)
    return 100.0 * least / dev if dev > 0 else None


def gmm_roofline(run: Run) -> Optional[float]:
    """Least time of the routed expert matmuls over the grouped-matmul
    kernel's device time, %, over the traced steps."""
    m, least = run.model, 0.0
    steps = run.traced_steps()
    for s in steps:
        calls = []
        if s.step.chunk_uid is not None:
            calls.append(s.chunk_real)
        if s.step.decode_ctx:
            calls.append(len(s.step.decode_ctx))
        for tokens in calls:
            f, b = wc.gmm_work(m, tokens)
            least += m["num_layers"] * wc.least_time(f, b, run.peaks)
    return _kernel_share(run, GMM_KERNEL, steps, least)


def paged_attn_roofline(run: Run) -> Optional[float]:
    """Least time of decode-only steps' paged attention over the paged
    kernel's device time inside those steps, %."""
    steps = [s for s in run.traced_steps() if s.step.kind == "decode"]
    least = sum(wc.least_time(*wc.paged_attn_work(run.model, s.step.decode_ctx),
                              run.peaks) for s in steps)
    return _kernel_share(run, PAGED_KERNEL, steps, least)
