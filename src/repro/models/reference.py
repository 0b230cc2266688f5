"""Plain float32 ``jax.numpy`` forward pass — the correctness reference.

Written apart from the serving stack on purpose: no kernel seam, no
cache, no capacity dispatch, no sharding. It computes causal
attention over the whole sequence and every routed expert densely
(unrouted experts get zero weight), in float32 at
``default_matmul_precision("highest")``, from the same parameter tree
the engine serves. The engine's logits after prefill and after decode
steps through its cache are checked against these.

Covers the attention families with a dense or MoE SwiGLU/GeGLU FFN and
no sliding window, softcaps or post-norms; anything else raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

_F32 = jnp.float32


def _check_supported(cfg: ModelConfig) -> None:
    unsupported = {
        "block_type": cfg.block_type != "attention",
        "causal": not cfg.causal,
        "frontend": cfg.frontend != "none",
        "sliding_window": cfg.sliding_window > 0,
        "attn_logit_softcap": cfg.attn_logit_softcap > 0,
        "final_logit_softcap": cfg.final_logit_softcap > 0,
        "use_post_norm": cfg.use_post_norm,
        "activation": cfg.activation not in ("silu", "gelu"),
        "ffn_type": cfg.ffn_type not in ("dense", "moe"),
    }
    bad = sorted(k for k, v in unsupported.items() if v)
    if bad:
        raise NotImplementedError(f"reference forward does not cover {bad}")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, H, D): rotate the two halves of D by position angles."""
    if theta <= 0:
        return x
    S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=_F32) / D)
    ang = jnp.arange(S, dtype=_F32)[:, None, None] * inv  # (S, 1, D/2)
    x1, x2 = x[..., : D // 2], x[..., D // 2 :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _act(cfg, x):
    if cfg.activation == "silu":
        return x * jax.nn.sigmoid(x)
    return jax.nn.gelu(x, approximate=True)


def _attention(cfg, lp, h):
    S = h.shape[0]
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _rope((h @ lp["wq"]).reshape(S, Hq, hd), cfg.rope_theta)
    k = _rope((h @ lp["wk"]).reshape(S, Hkv, hd), cfg.rope_theta)
    v = (h @ lp["wv"]).reshape(S, Hkv, hd)
    kv_head = jnp.arange(Hq) // (Hq // Hkv)
    k, v = k[:, kv_head], v[:, kv_head]  # (S, Hq, hd)
    scale = (cfg.query_pre_attn_scalar ** -0.5 if cfg.query_pre_attn_scalar
             else hd ** -0.5)
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, Hq * hd)
    return o @ lp["wo"]


def _glu(cfg, x, wg, wu, wo):
    return (_act(cfg, x @ wg) * (x @ wu)) @ wo


def _moe(cfg, mp, h):
    probs = jax.nn.softmax(h @ mp["router"], axis=-1)  # (S, E)
    top, idx = jax.lax.top_k(probs, cfg.top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    # dense combine weights: each token's renormalized gate on its top-k
    # experts, zero elsewhere
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)
    gate = _act(cfg, jnp.einsum("sd,edf->esf", h, mp["wi_gate"]))
    up = jnp.einsum("sd,edf->esf", h, mp["wi_up"])
    y_e = jnp.einsum("esf,efd->esd", gate * up, mp["wo"])
    y = jnp.einsum("se,esd->sd", gates, y_e)
    if cfg.n_shared_experts:
        y = y + _glu(cfg, h, mp["shared_wi_gate"], mp["shared_wi_up"],
                     mp["shared_wo"])
    return y


def reference_logits(params, cfg: ModelConfig, tokens) -> jax.Array:
    """Float32 logits ``(S, vocab)`` at every position of the 1-D token
    sequence ``tokens``, from ``params`` as ``init_params`` lays them
    out (stacked per-layer leaves). Weights are upcast one layer at a
    time inside the layer scan."""
    _check_supported(cfg)
    with jax.default_matmul_precision("highest"):
        return _forward(params, cfg, jnp.asarray(tokens, jnp.int32))


@functools.partial(jax.jit, static_argnums=1)
def _forward(params, cfg, tokens):
    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(_F32), lp)
        x = x + _attention(cfg, lp["attn"], _rms(x, lp["ln1"], cfg.norm_eps))
        h = _rms(x, lp["ln2"], cfg.norm_eps)
        if cfg.ffn_type == "moe":
            return x + _moe(cfg, lp["moe"], h), None
        f = lp["ffn"]
        return x + _glu(cfg, h, f["wi_gate"], f["wi_up"], f["wo"]), None

    x = params["embed"][tokens].astype(_F32)
    if cfg.scale_embeddings:
        x = x * cfg.d_model ** 0.5
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"].astype(_F32), cfg.norm_eps)
    if cfg.tie_embeddings:
        return x @ params["embed"].astype(_F32).T
    return x @ params["lm_head"].astype(_F32)
