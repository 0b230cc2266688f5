"""Plain float32 ``jax.numpy`` forward pass — the correctness reference.

Written apart from the serving stack on purpose: no kernel seam, no
cache, no capacity dispatch, no sharding. It computes causal
attention over the whole sequence and every routed expert densely
(unrouted experts get zero weight), in float32 at
``default_matmul_precision("highest")``, from the same parameter tree
the engine serves. The engine's logits after prefill and after decode
steps through its cache are checked against these.

Covers the attention families with a dense or MoE SwiGLU/GeGLU FFN and
no sliding window, softcaps or post-norms; anything else raises. That
includes multi-head latent attention (DeepSeek-V2, arXiv:2405.04434),
computed as published: each head's key and value decompressed from the
RMS-normed latent through W_kvb, the rope key shared by all heads, YaRN
positions and softmax scale; and leading dense-FFN layers before the MoE
ones.

Rope convention: the two halves of each rope block rotate
(``x1 * cos - x2 * sin``, ``x2 * cos + x1 * sin``). DeepSeek's own code
first de-interleaves the (even, odd) columns of q_pe and k_pe; that is a
fixed permutation of the rope columns of wq and wkv_a, invisible to random
weights, and the program and both references (this one and the
benchmark's) apply the same half-split rotation to the columns as stored.
"""
from __future__ import annotations

import functools
import math

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


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _inv_freq(cfg, D):
    """Rotary inverse frequencies; with YaRN, those of pair index above
    the correction range are divided by the factor, linear between."""
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, D, 2, dtype=_F32) / D)
    if not cfg.yarn_factor:
        return inv

    def index(rot):  # pair index where ``rot`` rotations fit the context
        return (D * math.log(cfg.yarn_original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(index(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(index(cfg.yarn_beta_slow)), D - 1)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=_F32) - low) / max(high - low, 1e-3),
                    0.0, 1.0)
    return inv / cfg.yarn_factor * ramp + inv * (1.0 - ramp)


def _rope(x, cfg):
    """x (S, H, D): rotate the two halves of D by position angles."""
    if cfg.rope_theta <= 0:
        return x
    S, _, D = x.shape
    ang = jnp.arange(S, dtype=_F32)[:, None, None] * _inv_freq(cfg, D)  # (S, 1, D/2)
    x1, x2 = x[..., : D // 2], x[..., D // 2 :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    if cfg.yarn_factor:
        m = (_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / _mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
        c, s = c * m, s * m
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _softmax_scale(cfg):
    if cfg.query_pre_attn_scalar:
        return cfg.query_pre_attn_scalar ** -0.5
    scale = cfg.head_dim ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= _mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _causal_attention(cfg, q, k, v):
    """q, k (S, H, Dqk), v (S, H, Dv) -> (S, H*Dv)."""
    S = q.shape[0]
    s = jnp.einsum("qhd,khd->hqk", q, k) * _softmax_scale(cfg)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(S, -1)


def _act(cfg, x):
    if cfg.activation == "silu":
        return x * jax.nn.sigmoid(x)
    return jax.nn.gelu(x, approximate=True)


def _attention(cfg, lp, h):
    if cfg.is_mla:
        return _mla(cfg, lp, h)
    S = h.shape[0]
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _rope((h @ lp["wq"]).reshape(S, Hq, hd), cfg)
    k = _rope((h @ lp["wk"]).reshape(S, Hkv, hd), cfg)
    v = (h @ lp["wv"]).reshape(S, Hkv, hd)
    kv_head = jnp.arange(Hq) // (Hq // Hkv)
    k, v = k[:, kv_head], v[:, kv_head]  # (S, Hq, hd)
    return _causal_attention(cfg, q, k, v) @ lp["wo"]


def _mla(cfg, lp, h):
    """Multi-head latent attention, decompressed: q (S, H, dn + dr); the
    latent c = RMSNorm(h W_kva[:, :r]) gives each head's key part and
    value through W_kvb; the rope key h W_kva[:, r:] is shared by all."""
    S = h.shape[0]
    H, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim)
    r = cfg.kv_lora_rank
    q = (h @ lp["wq"]).reshape(S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg)], -1)
    kv_a = h @ lp["wkv_a"]
    c = _rms(kv_a[:, :r], lp["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kv_a[:, None, r:], cfg)                       # (S, 1, dr)
    kv = (c @ lp["wkv_b"]).reshape(S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    return _causal_attention(cfg, q, k, kv[..., dn:]) @ lp["wo"]


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
        if "moe" in lp:
            return x + _moe(cfg, lp["moe"], h), None
        f = lp["ffn"]
        return x + _glu(cfg, h, f["wi_gate"], f["wi_up"], f["wo"]), None

    x = params["embed"][tokens].astype(_F32)
    if cfg.scale_embeddings:
        x = x * cfg.d_model ** 0.5
    for i in range(len(params.get("dense_layers", {}))):  # leading dense layers
        x, _ = layer(x, params["dense_layers"][str(i)])
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"].astype(_F32), cfg.norm_eps)
    if cfg.tie_embeddings:
        return x @ params["embed"].astype(_F32).T
    return x @ params["lm_head"].astype(_F32)
