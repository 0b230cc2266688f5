"""Operations and bytes the model requires, from the config alone.

Counts are of the work the model needs for the tokens a step serves,
whatever implements it: prompt tokens count, the padding the engine adds
in front of a prompt does not; rows of a decode step count only when they
decode; expert rows count only as routed (``top_k`` per token), never as
the capacity slabs a kernel runs. Attention context is every cache
position a row attends, padding included, since the served function
attends it. ``m`` is the ``model`` section of a config file.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

Model = Dict[str, Any]


def _dims(m: Model) -> Tuple[int, ...]:
    return (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["n_routed_experts"], m["top_k"], m["moe_d_ff"])


def token_flops(m: Model, ctx: int) -> float:
    """One token through one layer, attending ``ctx`` positions."""
    d, hq, hkv, hd, E, k, f = _dims(m)
    proj = 2 * d * (hq + 2 * hkv) * hd + 2 * hq * hd * d
    core = 4 * hq * hd * ctx  # scores and the weighted sum of values
    router = 2 * d * E
    routed = k * 6 * d * f  # gate, up, down per routed expert
    shared = m["n_shared_experts"] * 6 * d * m.get("shared_d_ff", 0)
    return proj + core + router + routed + shared


def head_flops(m: Model) -> float:
    return 2 * m["d_model"] * m["vocab_size"]


def sum_ctx(lo: int, hi: int) -> int:
    """Sum of ``p + 1`` over positions ``lo <= p < hi``."""
    return (hi * (hi + 1) - lo * (lo + 1)) // 2 if hi > lo else 0


def step_flops(m: Model, chunk: Tuple[int, int], decode_ctx: Iterable[int],
               chunk_sampled: bool) -> float:
    """A step: prompt positions ``chunk = (lo, hi)`` (padding excluded),
    one token per decoding row with its context, and the head at each
    sampled position."""
    d, hq, hkv, hd, E, k, f = _dims(m)
    lo, hi = chunk
    ctx = list(decode_ctx)
    n = max(hi - lo, 0) + len(ctx)
    per_tok = token_flops(m, 0)
    core = 4 * hq * hd * (sum_ctx(lo, hi) + sum(ctx))
    heads = len(ctx) + (1 if chunk_sampled else 0)
    return m["num_layers"] * (n * per_tok + core) + heads * head_flops(m)


def gmm_work(m: Model, tokens: int) -> Tuple[float, float]:
    """One MoE invocation of one layer over ``tokens`` routed tokens:
    (FLOPs of the routed rows, expert weight bytes). The bytes take every
    expert as read, which holds only when ``tokens * top_k >= 6 E``
    (an expert then goes untouched with probability under 0.3%); below
    that they are counted as 0, so the least time stays a lower bound."""
    d, hq, hkv, hd, E, k, f = _dims(m)
    flops = tokens * k * 6 * d * f
    item = 2 if m["dtype"] == "bfloat16" else 4
    touched = tokens * k >= 6 * E
    return flops, (3 * E * d * f * item if touched else 0)


def paged_attn_work(m: Model, decode_ctx: Iterable[int]) -> Tuple[float, float]:
    """One decode step's paged attention over all layers: (FLOPs, bytes
    of K/V at the live positions, the new token's included)."""
    d, hq, hkv, hd, E, k, f = _dims(m)
    ctx = list(decode_ctx)
    item = 2 if m["dtype"] == "bfloat16" else 4
    kv_tok = 2 * hkv * hd * item
    flops = sum(4 * hq * hd * c for c in ctx)
    # a row attending c positions reads the c - 1 cached ones and writes one
    nbytes = sum(c * kv_tok for c in ctx)
    return m["num_layers"] * flops, m["num_layers"] * nbytes


def least_time(flops: float, nbytes: float, peaks: Dict[str, float]) -> float:
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
