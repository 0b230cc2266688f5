"""The generator gives every seed the same work in another order."""

import json
from collections import Counter

import numpy as np
import pytest
from conftest import BENCH

import traffic as tg

MIXES = ["chat", "decode"]


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work_with_other_tokens(name):
    mix = _mix(name)
    a = tg.generate(mix, 30, 3_000_000_019, 1000)
    b = tg.generate(mix, 30, 17, 1000)
    assert len(a) == len(b) == tg.count(mix, 30)
    shape = lambda rs: [(r.due, len(r.prompt), r.max_new) for r in rs]  # noqa: E731
    assert shape(a) == shape(b)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # the sizes are shuffled within the run, not sorted
    assert [len(r.prompt) for r in a] != sorted(len(r.prompt) for r in a)
    assert len(Counter(r.max_new for r in a)) > 1


@pytest.mark.parametrize("name", MIXES)
def test_sizes_stay_in_their_clips_and_tokens_in_the_vocabulary(name):
    mix = _mix(name)
    reqs = tg.generate(mix, 30, 2**31 + 5, 500)
    p, g = mix["prompt"], mix["output"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(g["min"] <= r.max_new <= g["max"] for r in reqs)
    assert all(1 <= r.prompt.min() and r.prompt.max() < 500 for r in reqs)
    # the same seed gives the same requests, token for token
    again = tg.generate(mix, 30, 2**31 + 5, 500)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(reqs, again))


def test_open_loop_arrives_at_its_rate_and_offline_is_queued_at_once():
    chat = dict(_mix("chat"), rate_per_s=5.0)
    due = [r.due for r in tg.generate(chat, 40, 1, 100)]
    assert due[0] == 0.0 and due == sorted(due)
    assert 200 / 5.0 * 0.9 < due[-1] < 200 / 5.0 * 1.1
    assert all(r.due == 0.0 for r in tg.generate(_mix("decode"), 10, 1, 100))


def test_strata_follow_the_distribution():
    x = tg.stratified({"dist": "lognormal", "median": 100, "sigma": 0.5,
                       "min": 1, "max": 10**6}, 1001)
    assert np.median(x) == 100
    u = tg.stratified({"dist": "uniform", "min": 16, "max": 64}, 49)
    assert u.min() >= 16 and u.max() <= 64 and np.median(u) == 40
