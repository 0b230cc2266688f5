"""One general generator for every traffic mix.

A mix file gives the loop (``open``: requests fall due on a schedule;
``offline``: all are queued at once), the slot count, and the prompt and
output length distributions (``lognormal`` with ``median``/``sigma``, or
``uniform``, each clipped to ``min``..``max``). An open mix adds
``rate_per_s`` (Poisson arrivals); an offline mix adds
``queue_per_s``, the requests queued per second of window on top of the
slots, enough that the queue never runs dry; the first wave of slots is
served until its prompts are in the cache before the window opens.

Every seed gets the same sizes and gaps in the same order: sizes sit at
the mid-points of equal-probability strata of their distribution, and
the pairs and the gaps are ordered by permutations fixed for the mix;
the seed draws only the token ids. So two seeds do the same work and
differ only in its content. (Shuffling the order by the seed made the
chat cell's 95th-percentile TTFT differ 2x from seed to seed, by which
long prompts happened to fall due together.)
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class Req:
    due: float  # seconds after the window opens
    prompt: np.ndarray  # (n,) int32 token ids
    max_new: int


def stratified(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` sizes at the mid-points of ``n`` equal-probability strata."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def count(traffic: Dict[str, Any], seconds: float) -> int:
    """Requests a run of ``seconds`` generates."""
    if traffic["loop"] == "open":
        return math.ceil(traffic["rate_per_s"] * seconds) + 1
    if traffic["loop"] == "offline":
        return traffic["slots"] + math.ceil(traffic["queue_per_s"] * seconds)
    raise ValueError(f"unknown loop {traffic['loop']!r}")


def generate(traffic: Dict[str, Any], seconds: float, seed: int,
             vocab: int) -> List[Req]:
    """The run's requests, in due order."""
    n = count(traffic, seconds)
    prompts = stratified(traffic["prompt"], n)
    outputs = stratified(traffic["output"], n)
    fixed = np.random.default_rng(0)  # pairing and order belong to the mix
    pairs = list(zip(prompts, outputs[fixed.permutation(n)]))
    order = fixed.permutation(n)
    if traffic["loop"] == "open":
        q = (np.arange(n - 1) + 0.5) / (n - 1)
        gaps = -np.log1p(-q) / traffic["rate_per_s"]
        due = np.concatenate([[0.0], np.cumsum(gaps[fixed.permutation(n - 1)])])
    else:
        due = np.zeros(n)
    rng = np.random.default_rng(seed)
    reqs = []
    for t, i in zip(due, order):
        p, g = pairs[i]
        reqs.append(Req(float(t), rng.integers(1, vocab, int(p)).astype(np.int32),
                        int(g)))
    return reqs
