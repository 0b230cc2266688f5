"""Whether what the timed path served is correct.

Once the window has closed, every request still queued or live is
cancelled (a live one keeps the tokens it was served), and the program's
state is freed. A sample of the requests that were served tokens,
finished or cut at the close, drawn from the seed and holding the
longest of them, is run through the float32 reference
(``reference.py``) with its served tokens: prompt padded as the engine
pads it, then every served token but the last. At the position before
each served token the reference's best logit is compared with the
served token's. The number compared is the mean of that gap over every
sampled token (``COMPARED``); a run passes where it is at most the
cell's limit (``passes``). Greedy decoding serves the engine's best
token, so a gap can come only from the engine's rounding against
float32 (bfloat16 weights, activations and cache), or from a fault.

The control reads, at the same positions, the gap of the token that the
reference computed with float8 operands puts first, and is held to the
same limit by the same test.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import reference


COMPARED = "mean_gap"  # the statistic held to the cell's limit
# the others are printed beside it, by calibrate.py and on an earlier line
STATISTICS = {
    "widest_gap": lambda g: float(np.max(g)),
    "mean_gap": lambda g: float(np.mean(g)),
    "mismatch_share": lambda g: float(np.mean(g > 0)),
}


def statistics(gap_arrays: Sequence[np.ndarray]) -> Dict[str, float]:
    """Every statistic over the gaps of all sampled tokens together."""
    g = np.concatenate(list(gap_arrays)) if len(gap_arrays) else np.zeros(0)
    return {k: (f(g) if g.size else float("inf")) for k, f in STATISTICS.items()}


def passes(value: float, limit: float) -> bool:
    """The test every compared number is held to."""
    return value <= limit


def step_kind_drift(steps: Sequence, before: Dict[str, int],
                    after: Dict[str, int]) -> int:
    """How far the step kinds the harness recorded (``adapter.next_step``)
    miss the engine's own counters over the same steps: a chunk counts
    one prefill chunk, a fused step one prefill chunk, one fused step and
    one decode step, a decode step one decode step. 0 where they agree;
    otherwise every per-step metric would be read from mislabelled
    steps."""
    n = {k: sum(s.step.kind == k for s in steps) for k in ("chunk", "fused", "decode")}
    want = {"prefill_chunks": n["chunk"] + n["fused"], "fused_steps": n["fused"],
            "decode_steps": n["decode"] + n["fused"]}
    return sum(abs(after[k] - before[k] - v) for k, v in want.items())


def sample(recs: Sequence, seed: int, tokens: int, most: int) -> List:
    """Requests served tokens, finished (``ok``) or cut at the close
    (``cancelled``): the longest (prompt and output) first, then others
    in an order drawn from the seed, until ``tokens`` served tokens or
    ``most`` requests."""
    done = [r for r in recs if r.status in ("ok", "cancelled") and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.uid)
    longest = max(done, key=lambda r: (r.padded + len(r.tokens), -r.uid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 1])
    pick = [longest]
    for i in rng.permutation(len(rest)):
        if sum(len(r.tokens) for r in pick) >= tokens or len(pick) >= most:
            break
        pick.append(rest[i])
    return pick


def sequence(r) -> tuple:
    """(tokens fed to the reference, rows whose logits chose the served
    tokens): the engine's left padding with id 0, the prompt, then the
    served tokens but the last."""
    pad = r.padded - len(r.prompt)
    seq = np.concatenate([np.zeros(pad, np.int32), np.asarray(r.prompt, np.int32),
                          np.asarray(r.tokens[:-1], np.int32)])
    rows = np.arange(r.padded - 1, r.padded - 1 + len(r.tokens))
    return seq, rows


def gaps(params, m: Dict, r, control: bool = False, arch=None) -> Dict[str, np.ndarray]:
    """Per served token: the reference's best logit minus its logit of
    the served token (``served``); with ``control``, also of the token
    the float8 reference puts first (``control``); and the reference's
    margin between its best two logits (``margin``). Every array on the
    device keeps the reference's padded rows, so a run compiles a few
    programs however many lengths its sample holds; the padding is cut
    on the host."""
    seq, rows = sequence(r)
    n = len(rows)
    ref = reference.logits_padded(params, m, seq, rows, "f32", arch)
    toks = np.zeros(ref.shape[0], np.int32)
    toks[:n] = r.tokens
    served, margin = _served_gaps(ref, toks)
    out = {"served": np.asarray(served)[:n], "margin": np.asarray(margin)[:n]}
    if control:
        low = reference.logits_padded(params, m, seq, rows, "fp8", arch)
        out["control"] = np.asarray(_picked_gaps(ref, low))[:n]
    return out


@jax.jit
def _served_gaps(ref, toks):
    top2 = jax.lax.top_k(ref, 2)[0]
    at = jnp.take_along_axis(ref, toks[:, None], axis=1)[:, 0]
    return top2[:, 0] - at, top2[:, 0] - top2[:, 1]


@jax.jit
def _picked_gaps(ref, low):
    pick = jnp.argmax(low, axis=-1)
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]
