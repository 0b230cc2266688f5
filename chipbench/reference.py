"""The plain float32 forward pass that decides ``correct``.

A copy of the repository's ``models/reference.py`` at the time the
benchmark was written, kept here so that the yardstick does not move
with the program; it imports nothing of the program. It takes the
configuration as the dict of a config file's ``model`` section, the
weights as ``weights.make`` lays them out, and the architecture module
(``chipbench/arch/<name>.py``, ``spec.py``) whose ``layers`` runs the
layer stack between the embedding and the head here.

Matrix products run in float32 at ``default_matmul_precision("highest")``.
To fit one chip beside nothing else, the layer stacks run one layer at a
time, slicing and upcasting only the weights each product needs; the
helpers below are theirs.

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

import spec

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


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(mkey, params, x, rows, fp8):
    m = json.loads(mkey)
    x = _rms(x[rows], params["final_norm"].astype(_F32), m["norm_eps"])
    if m["tie_embeddings"]:
        return _mm(x, params["embed"].T, fp8)
    return _mm(x, params["lm_head"], fp8)


def logits_at(params, m: Dict[str, Any], tokens: Sequence[int],
              rows: Sequence[int], precision: str = "f32", arch=None) -> jax.Array:
    """Logits ``(len(rows), vocab)`` at positions ``rows`` of the 1-D
    token sequence ``tokens``."""
    return logits_padded(params, m, tokens, rows, precision, arch)[: len(rows)]


def logits_padded(params, m: Dict[str, Any], tokens: Sequence[int],
                  rows: Sequence[int], precision: str = "f32", arch=None) -> jax.Array:
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
        x = spec.resolve(arch).layers(m, params, x, fp8)
        return _head(mkey, params, x, jnp.asarray(want), fp8)


def reference_logits(params, m: Dict[str, Any], tokens: Sequence[int],
                     arch=None) -> jax.Array:
    """Float32 logits ``(S, vocab)`` at every position."""
    return logits_at(params, m, tokens, range(len(tokens)), arch=arch)
