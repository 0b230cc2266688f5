"""Grouped-query attention (multi-head where every query head has its own
K and V) in front of a routed-plus-shared MoE layer: DeepSeek-MoE,
Mixtral.

Per layer: RMS norm, attention with ``num_heads`` query heads over
``num_kv_heads`` K/V heads of ``head_dim``, rotary positions on the two
halves of each head; RMS norm, a softmax router over ``n_routed_experts``
whose top ``top_k`` gates are renormalized, gated experts of ``moe_d_ff``,
and ``n_shared_experts`` shared experts of ``shared_d_ff`` added. The
KV cache holds K and V of every K/V head for each token and layer.

What an architecture module gives is listed in ``spec.py``.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict

import jax
import jax.numpy as jnp

from reference import _act, _at, _fp8, _mm, _rms, _rope

Model = Dict[str, Any]

KERNELS = ("decode", "gmm")
ONES = ("ln1", "ln2", "final_norm")


def shapes(m: Model) -> Dict[str, Any]:
    L, d, V = m["num_layers"], m["d_model"], m["vocab_size"]
    hq, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    E, f = m["n_routed_experts"], m["moe_d_ff"]
    tree: Dict[str, Any] = {"embed": (V, d), "final_norm": (d,)}
    if not m["tie_embeddings"]:
        tree["lm_head"] = (d, V)
    moe = {
        "router": (L, d, E),
        "wi_gate": (L, E, d, f),
        "wi_up": (L, E, d, f),
        "wo": (L, E, f, d),
    }
    if m["n_shared_experts"]:
        sf = m["shared_d_ff"] * m["n_shared_experts"]
        moe.update(shared_wi_gate=(L, d, sf), shared_wi_up=(L, d, sf),
                   shared_wo=(L, sf, d))
    tree["layers"] = {
        "ln1": (L, d),
        "ln2": (L, d),
        "attn": {"wq": (L, d, hq * hd), "wk": (L, d, hkv * hd),
                 "wv": (L, d, hkv * hd), "wo": (L, hq * hd, d)},
        "moe": moe,
    }
    return tree


def kv_bytes(m: Model) -> int:
    item = 2 if m["dtype"] == "bfloat16" else 4
    return 2 * m["num_kv_heads"] * m["head_dim"] * item


def attn_proj_flops(m: Model) -> int:
    """q, k, v and o projections."""
    d, hq, hkv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    return 2 * d * (hq + 2 * hkv) * hd + 2 * hq * hd * d


def attn_core_flops(m: Model) -> int:
    """Scores and the weighted sum of values."""
    return 4 * m["num_heads"] * m["head_dim"]


def attn_cache_bytes(m: Model) -> int:
    """K and V of the position."""
    return kv_bytes(m)


def _attention(m, a, l, h, fp8):
    S = h.shape[0]
    Hq, Hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = _rope(_mm(h, _at(a["wq"], l), fp8).reshape(S, Hq, hd), m["rope_theta"])
    k = _rope(_mm(h, _at(a["wk"], l), fp8).reshape(S, Hkv, hd), m["rope_theta"])
    v = _mm(h, _at(a["wv"], l), fp8).reshape(S, Hkv, hd)
    kv_head = jnp.arange(Hq) // (Hq // Hkv)
    k, v = k[:, kv_head], v[:, kv_head]
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if fp8:
        p = _fp8(p, -1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, Hq * hd)
    return _mm(o, _at(a["wo"], l), fp8)


def _moe(m, mp, l, h, fp8):
    probs = jax.nn.softmax(_mm(h, _at(mp["router"], l), fp8), axis=-1)
    top, idx = jax.lax.top_k(probs, m["top_k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(top)

    def expert(y, e):
        def w(name):
            leaf = mp[name]
            blk = jax.lax.dynamic_slice(
                leaf, (l, e, 0, 0), (1, 1) + leaf.shape[2:])
            return blk[0, 0].astype(jnp.float32)

        g = _act(m, _mm(h, w("wi_gate"), fp8)) * _mm(h, w("wi_up"), fp8)
        return y + gates[:, e, None] * _mm(g, w("wo"), fp8), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(m["n_routed_experts"]))
    if m["n_shared_experts"]:
        g = (_act(m, _mm(h, _at(mp["shared_wi_gate"], l), fp8))
             * _mm(h, _at(mp["shared_wi_up"], l), fp8))
        y = y + _mm(g, _at(mp["shared_wo"], l), fp8)
    return y


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(mkey, layers, l, x, fp8):
    m = json.loads(mkey)
    eps = m["norm_eps"]
    x = x + _attention(m, layers["attn"], l, _rms(x, _at(layers["ln1"], l), eps), fp8)
    h = _rms(x, _at(layers["ln2"], l), eps)
    return x + _moe(m, layers["moe"], l, h, fp8)


def layers(m: Model, params, x, fp8: bool):
    """One layer at a time, one routed expert at a time inside it."""
    mkey = json.dumps(m, sort_keys=True)
    for l in range(m["num_layers"]):
        x = _layer(mkey, params["layers"], jnp.int32(l), x, fp8)
    return x
