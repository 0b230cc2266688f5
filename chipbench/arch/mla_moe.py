"""Multi-head latent attention in front of a routed-plus-shared MoE layer,
after leading dense-FFN layers: DeepSeek-V2-Lite (arXiv:2405.04434).

Per layer: RMS norm, then attention over ``num_heads`` heads whose queries
are ``qk_nope_head_dim + qk_rope_head_dim`` wide (``wq``); one latent row a
token, ``h · wkv_a``: the compression ``c`` (``kv_lora_rank``), RMS-normed
by ``kv_norm``, and a ``qk_rope_head_dim`` key ``k_pe`` that every head
shares; each head's key ``[c · W_UK, k_pe]`` and value ``c · W_UV``
(``wkv_b``, per head ``qk_nope_head_dim`` key columns then
``v_head_dim`` value columns); causal softmax; ``wo``. Positions are YaRN
rope (``yarn_*``, ``rope_theta``) on q_pe and k_pe, and YaRN scales the
softmax. Then RMS norm and the FFN: a dense SwiGLU of ``d_ff`` in the
first ``first_k_dense_replace`` layers, then a softmax router over
``n_routed_experts`` whose top ``top_k`` gates are renormalized, gated
experts of ``moe_d_ff``, and ``n_shared_experts`` shared experts of
``shared_d_ff``. The KV cache holds the latent row, ``c`` and ``k_pe``:
one row a token and layer, key and value at once.

Rope rotates the two halves of each rope block. DeepSeek's code first
de-interleaves the (even, odd) columns of q_pe and k_pe, a fixed
permutation of wq's and wkv_a's rope columns that random weights do not
see; the program and its own reference rotate the columns as stored, and
so does this one.

Attention runs in blocks of queries: at 12k positions the float32 scores
of all 16 heads at once would not fit beside the weights. What an
architecture module gives is listed in ``spec.py``.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from reference import _act, _at, _fp8, _mm, _rms

Model = Dict[str, Any]

KERNELS = ("mla_decode", "gmm")
ONES = ("ln1", "ln2", "final_norm", "kv_norm")
_Q_BLOCK = 512  # queries a block of the reference's attention


def _attn_shapes(m: Model, lead: tuple) -> Dict[str, Any]:
    d, H, r = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return {"wq": lead + (d, H * (dn + dr)), "wkv_a": lead + (d, r + dr),
            "kv_norm": lead + (r,), "wkv_b": lead + (r, H * (dn + dv)),
            "wo": lead + (H * dv, d)}


def shapes(m: Model) -> Dict[str, Any]:
    K = m["first_k_dense_replace"]
    L, d, V = m["num_layers"] - K, m["d_model"], m["vocab_size"]
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
    tree["layers"] = {"ln1": (L, d), "ln2": (L, d), "attn": _attn_shapes(m, (L,)),
                      "moe": moe}
    if K:  # one unstacked tree per leading dense layer
        F = m["d_ff"]
        tree["dense_layers"] = {
            str(i): {"ln1": (d,), "ln2": (d,), "attn": _attn_shapes(m, ()),
                     "ffn": {"wi_gate": (d, F), "wi_up": (d, F), "wo": (F, d)}}
            for i in range(K)}
    return tree


def kv_bytes(m: Model) -> int:
    item = 2 if m["dtype"] == "bfloat16" else 4
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * item


def attn_proj_flops(m: Model) -> int:
    """q, kv_a, kv_b and o projections (kv_b's count is also that of the
    absorbed decode's two per-head products, W_UK into q and W_UV after)."""
    d, H, r = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return 2 * d * H * (dn + dr) + 2 * d * (r + dr) + 2 * r * H * (dn + dv) + 2 * H * dv * d


def attn_core_flops(m: Model) -> int:
    """Absorbed: each head's score against the whole latent row, and its
    weighted sum of the row's first ``kv_lora_rank`` values."""
    r = m["kv_lora_rank"]
    return 2 * m["num_heads"] * (r + m["qk_rope_head_dim"] + r)


def attn_cache_bytes(m: Model) -> int:
    """The position's latent row."""
    return kv_bytes(m)


# -- the float32 stack ----------------------------------------------------------
def _mscale(factor: float, ms: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * ms * math.log(factor) + 1.0


def _rope(m, x):
    """x (S, H, D): YaRN rope, the two halves of D rotated."""
    S, _, D = x.shape
    theta, factor = m["rope_theta"], m["yarn_factor"]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)

    def index(rot):  # the pair index at which ``rot`` rotations fit the context
        return (D * math.log(m["yarn_original_max_position"] / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(index(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(index(m["yarn_beta_slow"])), D - 1)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3),
                    0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    scale = _mscale(factor, m["yarn_mscale"]) / _mscale(factor, m["yarn_mscale_all_dim"])
    c, s = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(m, a, l, h, fp8):
    S = h.shape[0]
    H, r = m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q = _mm(h, _at(a["wq"], l), fp8).reshape(S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(m, q[..., dn:])], -1)
    kv_a = _mm(h, _at(a["wkv_a"], l), fp8)
    c = _rms(kv_a[:, :r], _at(a["kv_norm"], l), m["norm_eps"])
    k_pe = _rope(m, kv_a[:, None, r:])
    kv = _mm(c, _at(a["wkv_b"], l), fp8).reshape(S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    v = kv[..., dn:]
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
    scale = (dn + dr) ** -0.5 * _mscale(m["yarn_factor"], m["yarn_mscale_all_dim"]) ** 2
    nb = S // _Q_BLOCK

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * _Q_BLOCK, _Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        qpos = i * _Q_BLOCK + jnp.arange(_Q_BLOCK)
        p = jax.nn.softmax(jnp.where(jnp.arange(S)[None, :] <= qpos[:, None], s, -jnp.inf),
                           axis=-1)
        if fp8:
            p = _fp8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(S, H * dv)
    return _mm(o, _at(a["wo"], l), fp8)


def _moe(m, mp, l, h, fp8):
    probs = jax.nn.softmax(_mm(h, _at(mp["router"], l), fp8), axis=-1)
    top, idx = jax.lax.top_k(probs, m["top_k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(top)

    def expert(y, e):
        def w(name):
            leaf = mp[name]
            blk = jax.lax.dynamic_slice(leaf, (l, e, 0, 0), (1, 1) + leaf.shape[2:])
            return blk[0, 0].astype(jnp.float32)

        g = _act(m, _mm(h, w("wi_gate"), fp8)) * _mm(h, w("wi_up"), fp8)
        return y + gates[:, e, None] * _mm(g, w("wo"), fp8), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(m["n_routed_experts"]))
    if m["n_shared_experts"]:
        g = (_act(m, _mm(h, _at(mp["shared_wi_gate"], l), fp8))
             * _mm(h, _at(mp["shared_wi_up"], l), fp8))
        y = y + _mm(g, _at(mp["shared_wo"], l), fp8)
    return y


def _dense(m, f, l, h, fp8):
    g = _act(m, _mm(h, _at(f["wi_gate"], l), fp8)) * _mm(h, _at(f["wi_up"], l), fp8)
    return _mm(g, _at(f["wo"], l), fp8)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _layer(mkey, layers, l, x, fp8, dense):
    m = json.loads(mkey)
    eps = m["norm_eps"]
    x = x + _attention(m, layers["attn"], l, _rms(x, _at(layers["ln1"], l), eps), fp8)
    h = _rms(x, _at(layers["ln2"], l), eps)
    if dense:
        return x + _dense(m, layers["ffn"], l, h, fp8)
    return x + _moe(m, layers["moe"], l, h, fp8)


def layers(m: Model, params, x, fp8: bool):
    """One layer at a time: the dense ones, then the MoE ones, one routed
    expert at a time inside those."""
    mkey = json.dumps(m, sort_keys=True)
    for l in range(m["first_k_dense_replace"]):
        one = jax.tree.map(lambda a: a[None], params["dense_layers"][str(l)])
        x = _layer(mkey, one, jnp.int32(0), x, fp8, True)
    for l in range(m["num_layers"] - m["first_k_dense_replace"]):
        x = _layer(mkey, params["layers"], jnp.int32(l), x, fp8, False)
    return x
