"""The readers of what the program records, on a hand-made run and a
hand-made recorder; and each reader's None where the program records
nothing or has no tracing module (as a parent commit may not)."""

import importlib.util
import sys

import numpy as np
import pytest

import devtrace as tr
import program_records as pr
import readings as rd
import serving_loop
from adapter import NextStep
from conftest import BENCH

trace = pytest.importorskip("repro.serving.trace")

NEW = ("admit_wait_p95_ms.chat", "prefill_wait_p95_ms.chat", "step_host_ms.decode",
       "step_host_ms.chat")


def _reader(name):
    spec = importlib.util.spec_from_file_location(f"_m_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _harness():
    rec = serving_loop.Recorder(bucket=16)
    for uid, due in enumerate((10.0, 11.0, 12.0, 19.0, 9.0)):  # uid 4 due before the window
        rec.submitted(uid, due, np.ones(8, np.int32), 4)
    # traced steps, on the harness's clock: a decode step and a fused one
    rec.steps.append(serving_loop.StepRecord(14.0, 14.1, NextStep("decode", None, 0, 0, [9]),
                                             0, True))
    rec.steps.append(serving_loop.StepRecord(14.2, 14.4, NextStep("fused", 2, 0, 16, [9]),
                                             8, True))
    return rec


def _program():
    prog = trace.Recorder()
    times = {  # uid: submitted, joined, first chunk
        0: (10.01, 10.02, 10.5),
        1: (11.01, 11.5, 12.5),
        2: (12.01, 12.03, 14.2),
        3: (19.01, None, None),  # not admitted by the close (20.0)
        4: (9.01, 9.02, 9.1),
    }
    for uid, (sub, joined, first) in times.items():
        prog.submitted(uid)
        r = prog.get(uid)
        r.submitted, r.joined, r.first_chunk = sub, joined, first
    S = trace.Span
    prog.spans.extend([
        S("engine.step", 14.00, 14.10, None, 1, {"kind": "decode"}),
        S("engine.inputs", 14.00, 14.01, 1, 2, {}),
        S("engine.dispatch", 14.01, 14.02, 1, 3, {}),
        S("engine.sync", 14.03, 14.09, 1, 4, {}),
        S("engine.book", 14.09, 14.10, 1, 5, {}),
        S("engine.step", 14.20, 14.40, None, 6, {"kind": "fused"}),
        S("engine.sync", 14.25, 14.37, 6, 7, {}),
        S("engine.step", 16.00, 16.05, None, 8, {"kind": "decode"}),  # after the trace
    ])
    return prog


def _run(prog=None, trace_ops=None):
    run = rd.Run(model={}, traffic={"slots": 4}, peaks={}, rec=_harness(),
                 window=(10.0, 20.0), due_until=20.0)
    run.traced = (13.0, 15.0)
    run.offset = 100.0  # trace clock = perf_counter + 100
    if trace_ops is not None:
        run.trace = tr.Trace({0: [tr.Event("op", a + 100.0, b + 100.0) for a, b in trace_ops]},
                             [])
    if prog is not None:
        trace.install(prog)
    return run


@pytest.fixture(autouse=True)
def _restore_current():
    old = trace.current()
    yield
    trace.install(old)


def test_admit_and_prefill_waits():
    run = _run(_program())
    # uid 3 never joined: its age at the close, 20.0 - 19.01
    assert np.round(sorted(pr.admit_waits(run)), 6).tolist() == [0.01, 0.02, 0.49, 0.99]
    # uid 3 not joined: left out
    assert np.round(sorted(pr.prefill_waits(run)), 6).tolist() == [0.48, 1.0, 2.17]
    assert _reader("admit_wait_p95_ms.chat")(run) == pytest.approx(
        rd.pctl([10, 20, 490, 990], 95))
    assert _reader("prefill_wait_p95_ms.chat")(run) == pytest.approx(
        rd.pctl([480, 1000, 2170], 95))


def test_prefill_wait_counts_a_first_chunk_past_the_close():
    prog = _program()
    prog.get(2).first_chunk = None
    run = _run(prog)
    assert max(pr.prefill_waits(run)) == pytest.approx(20.0 - 12.03)


def test_step_host_ms_is_the_step_less_its_sync():
    run = _run(_program())
    # decode: 100 ms - 60 ms of sync; the step after the trace is left out
    assert _reader("step_host_ms.decode")(run) == pytest.approx(40.0)
    # fused: 200 ms - 120 ms
    assert _reader("step_host_ms.chat")(run) == pytest.approx(80.0)


def test_idle_time_goes_to_the_innermost_program_span():
    # device busy over the dispatch and most of the sync; idle elsewhere
    ops = [(14.01, 14.02), (14.025, 14.085), (14.21, 14.36)]
    run = _run(_program(), trace_ops=ops)
    got = pr.idle_by_span(run)
    want = {"engine.inputs": 0.01, "engine.step": 0.005, "engine.sync": 0.005,
            "engine.book": 0.01}
    want_fused = {"engine.step": 0.01 + 0.03, "engine.sync": 0.01}
    for k, v in want_fused.items():
        want[k] = want.get(k, 0.0) + v
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v, abs=1e-9), k
    # every idle second inside the traced steps is put down somewhere
    total = sum(b - a for s in run.traced_steps()
                for a, b in tr.gaps(run.trace, *run.on_trace(s)))
    assert sum(got.values()) == pytest.approx(total)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_none_from_an_empty_recorder(name):
    assert _reader(name)(_run(trace.Recorder())) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_none_without_the_tracing_module(name, monkeypatch):
    import repro.serving

    run = _run(_program())
    monkeypatch.delattr(repro.serving, "trace")
    monkeypatch.setitem(sys.modules, "repro.serving.trace", None)
    assert pr.recorder() is None
    assert _reader(name)(run) is None
