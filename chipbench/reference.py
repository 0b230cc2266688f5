"""The plain float32 forward pass that decides ``correct``.

A copy of the repository's ``models/reference.py`` at the time the
benchmark was written, kept here so that the yardstick does not move
with the program; it imports nothing of the program. It takes the
configuration as the dict of a config file's ``model`` section and the
weights as ``weights.make`` lays them out.

Causal attention over the whole sequence, every routed expert computed
densely for every token and weighted by its renormalized top-k gate
(zero off the top-k), shared experts added, RMS norms, rotary positions
on the two halves of each head. Matrix products run in float32 at
``default_matmul_precision("highest")``. To fit one chip beside nothing
else, it runs one layer at a time and, inside a layer, one expert at a
time, slicing and upcasting only that expert's bfloat16 weights.

``precision="fp8"`` is the control: every matrix product takes its
operands rounded to float8 e4m3 (absmax-scaled per row of activations and
per output column of weights), accumulating in float32.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
_FP8_MAX = 448.0  # largest finite float8_e4m3fn
_SEQ_BLOCK = 512  # sequence lengths are padded to a multiple of this
_ROW_BLOCK = 128  # and the rows read back to a multiple of this


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(_F32) * s


def _mm(a, b, fp8: bool):
    """a (..., k) @ b (k, n)."""
    a, b = a.astype(_F32), b.astype(_F32)
    if fp8:
        a, b = _fp8(a, -1), _fp8(b, 0)
    return a @ b


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, H, D): rotate the two halves of D by position angles."""
    S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=_F32) / D)
    ang = jnp.arange(S, dtype=_F32)[:, None, None] * inv
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _act(m, x):
    if m["activation"] == "silu":
        return x * jax.nn.sigmoid(x)
    return jax.nn.gelu(x, approximate=True)


def _at(w, l):
    """Layer ``l`` of a stacked leaf, as float32."""
    return jax.lax.dynamic_index_in_dim(w, l, 0, keepdims=False).astype(_F32)


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
            return blk[0, 0].astype(_F32)

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


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(mkey, params, x, rows, fp8):
    m = json.loads(mkey)
    x = _rms(x[rows], params["final_norm"].astype(_F32), m["norm_eps"])
    if m["tie_embeddings"]:
        return _mm(x, params["embed"].T, fp8)
    return _mm(x, params["lm_head"], fp8)


def logits_at(params, m: Dict[str, Any], tokens: Sequence[int],
              rows: Sequence[int], precision: str = "f32") -> jax.Array:
    """Logits ``(len(rows), vocab)`` at positions ``rows`` of the 1-D
    token sequence ``tokens``."""
    return logits_padded(params, m, tokens, rows, precision)[: len(rows)]


def logits_padded(params, m: Dict[str, Any], tokens: Sequence[int],
                  rows: Sequence[int], precision: str = "f32") -> jax.Array:
    """As ``logits_at``, with the rows padded at the end to a multiple of
    ``_ROW_BLOCK`` (the last row repeated): the first ``len(rows)`` are
    the logits asked for."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    fp8 = precision == "fp8"
    mkey = json.dumps(m, sort_keys=True)
    # lengths are padded at the end to a few shapes, so a run compiles a
    # handful of programs: causal attention leaves earlier positions as
    # they are, and the padded rows are dropped
    toks = np.zeros(-(-len(tokens) // _SEQ_BLOCK) * _SEQ_BLOCK, np.int32)
    toks[: len(tokens)] = tokens
    rows = np.asarray(rows, np.int32)
    want = np.full(-(-len(rows) // _ROW_BLOCK) * _ROW_BLOCK, rows[-1], np.int32)
    want[: len(rows)] = rows
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(toks)].astype(_F32)
        for l in range(m["num_layers"]):
            x = _layer(mkey, params["layers"], jnp.int32(l), x, fp8)
        return _head(mkey, params, x, jnp.asarray(want), fp8)


def reference_logits(params, m: Dict[str, Any], tokens: Sequence[int]) -> jax.Array:
    """Float32 logits ``(S, vocab)`` at every position."""
    return logits_at(params, m, tokens, range(len(tokens)))
