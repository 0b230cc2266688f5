"""What the check samples, and the step kinds held against the engine's
counters, on hand-made records."""

import numpy as np

import check
import serving_loop
from adapter import NextStep


def _recs():
    rec = serving_loop.Recorder(bucket=16)
    for uid, n in enumerate([10, 40, 20, 5, 30]):
        rec.submitted(uid, 0.0, np.ones(n, np.int32), 8)
    done = {0: ("ok", [1] * 8), 1: ("cancelled", [2] * 5),  # cut live at the close
            2: ("cancelled", []),  # cut while queued: nothing served
            3: ("deadline", [3] * 2)}  # uid 4 never retired
    for uid, (status, toks) in done.items():
        rec.reqs[uid].status, rec.reqs[uid].tokens = status, toks
    return rec


def test_the_sample_takes_finished_and_cut_requests_longest_first():
    picked = check.sample(_recs().reqs.values(), seed=5, tokens=100, most=10)
    assert [r.uid for r in picked][0] == 1  # padded 48 + 5 tokens
    assert sorted(r.uid for r in picked) == [0, 1]


def test_the_sample_stops_at_its_token_budget():
    picked = check.sample(_recs().reqs.values(), seed=5, tokens=5, most=10)
    assert [r.uid for r in picked] == [1]


def test_every_statistic_is_over_all_sampled_tokens():
    s = check.statistics([np.array([0.0, 0.3]), np.array([0.0, 0.0, 0.2])])
    assert s == {"widest_gap": 0.3, "mean_gap": 0.1, "mismatch_share": 0.4}
    assert check.passes(s[check.COMPARED], 0.1)
    assert not check.passes(s[check.COMPARED], 0.099)


def _stepped(kinds):
    rec = serving_loop.Recorder(bucket=16)
    rec.submitted(0, 0.0, np.ones(40, np.int32), 8)
    for k in kinds:
        rec.stepped(0.0, 0.1, NextStep(k, 0 if k != "decode" else None, 0,
                                       16 if k != "decode" else 0, []), [])
    return rec.steps


def test_step_kinds_agree_with_the_engine_counters():
    steps = _stepped(["chunk", "fused", "fused", "decode", "decode", "decode"])
    before = {"prefill_chunks": 4, "fused_steps": 1, "decode_steps": 7}
    after = {"prefill_chunks": 7, "fused_steps": 3, "decode_steps": 12}
    assert check.step_kind_drift(steps, before, after) == 0
    # a fused step read as a chunk alone misses two counters by one each
    steps = _stepped(["chunk", "chunk", "fused", "decode", "decode", "decode"])
    assert check.step_kind_drift(steps, before, after) == 2
