"""The trace reduction: busy/idle union, kernel time by name, idle gaps
named by the harness span open at the time."""

import json
from pathlib import Path

import pytest

import devtrace as tr

E = tr.Event
RECORDED = Path(__file__).resolve().parent / "data" / "trace_extract.json"


def test_union_and_coverage():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.covered([(0, 2.5), (3, 4)], 1, 3.5) == pytest.approx(2.0)


def test_busy_gaps_and_span_names():
    ops = {0: [E("a", 0.0, 1.0), E("b", 0.5, 2.0), E("c", 3.0, 3.5)],
           1: [E("a", 0.0, 3.5)]}
    spans = [E("bench.step.decode", 0.0, 2.2), E("bench.retire", 2.2, 2.6),
             E("bench.idle", 2.6, 3.0)]
    t = tr.Trace(ops, spans)
    assert tr.busy(t, 0.0, 4.0) == pytest.approx((2.5 + 3.5) / 2)
    assert tr.gaps(t, 0.0, 4.0) == [(2.0, 3.0), (3.5, 4.0)]
    assert tr.span_at(spans, 2.5) == "bench.retire"
    assert tr.span_at(spans, 5.0) == "none"
    assert tr.top_gaps(t, 0.0, 4.0) == [["bench.retire", 1.0], ["none", 0.5]]
    assert tr.idle_by_span(t, 0.0, 4.0) == {"bench.retire": 1.0, "none": 0.5}
    top = tr.top_ops(t, 0.0, 4.0, n=2)
    assert top[0][0] == "a" and top[0][1] == pytest.approx((1.0 + 3.5) / 2)
    assert tr.op_time_in(t, lambda n: n == "b", [(0.4, 0.6)]) == pytest.approx(1.5 / 2)
    assert tr.op_time_in(t, lambda n: n == "b", [(0.6, 0.9)]) == 0.0


def test_innermost_span_names_a_gap():
    spans = [E("bench.step.chunk", 0.0, 10.0), E("bench.mark", 4.0, 4.1)]
    assert tr.span_at(spans, 4.05) == "bench.mark"
    assert tr.span_at(spans, 5.0) == "bench.step.chunk"


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace extract")
def test_recorded_trace_reduces():
    """0.3 s of a traced Mixtral decode window on one v5e chip: device ops
    as the trace names them, and the harness's spans."""
    d = json.loads(RECORDED.read_text())
    t = tr.Trace({int(k): [E(tr.short(e[0]), e[1], e[2]) for e in v]
                  for k, v in d["ops"].items()}, [E(*s) for s in d["spans"]])
    steps = [s for s in t.spans if s.name == "bench.step.decode"]
    lo, hi = steps[0].start, steps[-1].end
    b = tr.busy(t, lo, hi)
    assert 0.5 * (hi - lo) < b <= hi - lo  # decode keeps the chip busy
    idle = sum(tr.idle_by_span(t, lo, hi).values())
    assert b + idle == pytest.approx(hi - lo)
    assert all(name.startswith("bench.") or name == "none"
               for name, _ in tr.top_gaps(t, lo, hi))
    names = {e.name.split(".")[0] for e in t.ops[0]}
    assert {"grouped_matmul", "paged_attention", "while"} <= names
    # a loop's event holds its body's ops: ranking counts the body only
    top = [n for n, _ in tr.top_ops(t, lo, hi)]
    assert not any(n.startswith("while") for n in top)
    paged = tr.op_time_in(t, lambda n: n.startswith("paged_attention"),
                          [(s.start, s.end) for s in steps])
    assert 0 < paged < b


def test_short_names():
    assert tr.short("%grouped_matmul.44 = bf16[64,512,2048]{2,1,0} custom-call(x)") \
        == "grouped_matmul.44"
    assert tr.short("fusion.3") == "fusion.3"


def test_leaves_drop_enclosing_loops():
    ops = [E("while.1", 0.0, 10.0), E("a", 1.0, 2.0), E("b", 3.0, 4.0), E("c", 11.0, 12.0)]
    assert [e.name for e in tr.leaves(ops)] == ["a", "b", "c"]
