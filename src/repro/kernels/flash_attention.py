"""Pallas TPU flash attention (prefill hot spot of the Attention module).

Online-softmax tiled attention with causal, sliding-window and
logit-softcap support, GQA-aware (kv head = q head // group).

TPU mapping: grid (B, Hq, Sq/bq, Sk/bk) with the kv axis innermost and
sequential (carry in VMEM scratch); q/k/v tiles live in VMEM via BlockSpec,
tile sizes from ``tile_size`` (aligned, or the whole sequence; ragged
lengths are padded and the padded keys masked). Scratch: f32 accumulator (bq, hd) + running
max/sum (bq,) — the standard FlashAttention-2 recurrence.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


SUBLANE = 8  # second-minor block dims: multiples of this, or the full dim
LANE = 128  # minor block dims: multiples of this, or the full dim


def tile_size(n: int, pref: int, align: int) -> Tuple[int, int]:
    """``(tile, padded_n)`` for a dimension of size ``n``.

    The TPU lowering accepts a block dim that is a multiple of ``align``
    (``SUBLANE`` or ``LANE``, by the dim's place in its block) or equal
    to the whole dimension. A dim no larger than ``pref`` is taken whole;
    otherwise the tile is the largest multiple of ``align`` up to
    ``pref`` that divides ``n``. Where none does, ``n`` is padded up to a
    multiple of ``align`` and tiled the same way — the caller pads the
    operand and slices the result, never falling back to an unaligned
    tile.
    """
    if n <= pref:
        return n, n
    padded = -(-n // align) * align
    t = max(align, pref - pref % align)
    while padded % t:
        t -= align
    return t, padded


def pad_dim(x: jax.Array, axis: int, size: int) -> jax.Array:
    """Zero-pad ``x`` along ``axis`` up to ``size``."""
    extra = size - x.shape[axis]
    if extra == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths)


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    bq: int,
    bk: int,
    n_kv: int,
    q_offset: int,
    kv_len: int,
):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block (sequential, innermost)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)  # (bk, hd)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    # queries align to the END of the kv sequence when Sq != Sk
    qpos = q_offset + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = kpos < kv_len  # padded keys never score
    if causal:
        ok &= kpos <= qpos
        if window > 0:
            ok &= (qpos - kpos) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_cur

    @pl.when(j == n_kv - 1)
    def _finalize():
        lse = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / lse[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool,
) -> jax.Array:
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Sk, hd) -> (B, Hq, Sq, hd).

    ``interpret`` has no default: only a caller off the TPU asks for the
    Pallas interpreter."""
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = hd**-0.5
    bq, sq_p = tile_size(Sq, bq, SUBLANE)
    bk, sk_p = tile_size(Sk, bk, SUBLANE)
    q = pad_dim(q, 2, sq_p)
    k = pad_dim(k, 2, sk_p)
    v = pad_dim(v, 2, sk_p)
    n_kv = sk_p // bk
    grid = (B, Hq, sq_p // bq, n_kv)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        bq=bq,
        bk=bk,
        n_kv=n_kv,
        q_offset=Sk - Sq,
        kv_len=Sk,
    )

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
