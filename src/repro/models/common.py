"""Shared numerical building blocks for the model zoo."""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6,
             gemma_style: bool = False) -> jax.Array:
    """RMSNorm computed in float32, cast back to input dtype.

    ``gemma_style=True`` uses the (1 + w) parameterization of the Gemma
    family; both start from zero-centered init in this repo, so gemma style
    initializes w at 0 and others at 1 (handled at init time - here we only
    apply).
    """
    dtype = x.dtype
    # variance in f32 for stability, but the normalize-multiply stays in
    # the input dtype: upcasting the whole (B, S, d) tensor would make the
    # TP-axis collectives (SP all-gather, partial-sum all-reduce) move f32
    # — 2x the wire bytes.
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(dtype)
    w = weight.astype(dtype)
    scale = (1.0 + w) if gemma_style else w
    return x * inv * scale


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 style logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0.0:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


class Yarn(NamedTuple):
    """YaRN rotary scaling (arXiv:2309.00071), as DeepSeek-V2 configures
    it (``rope_scaling`` of type ``yarn``)."""
    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor ``0.1 * mscale * ln(factor) + 1`` (1 where
    the factor does not stretch)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, yarn: Yarn) -> Tuple[int, int]:
    """The rotary pair indices between which YaRN ramps from the original
    inverse frequency (below ``low``: ``beta_fast`` rotations or more over
    the original context) to the interpolated one (above ``high``: fewer
    than ``beta_slow``)."""
    def index(rotations: float) -> float:
        return (dim * math.log(yarn.original_max_position / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(index(yarn.beta_fast)), 0)
    high = min(math.ceil(index(yarn.beta_slow)), dim - 1)
    return low, high


def rope_frequencies(head_dim: int, theta: float,
                     yarn: Optional[Yarn] = None) -> jax.Array:
    """Inverse frequencies for rotary embeddings, shape (head_dim // 2,).
    With ``yarn``: divided by its factor above the correction range, as
    they are below it, linear in the pair index between."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv = 1.0 / (theta ** exponent)
    if yarn is None:
        return inv
    low, high = yarn_correction_range(head_dim, theta, yarn)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return inv / yarn.factor * ramp + inv * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn: Optional[Yarn] = None) -> jax.Array:
    """Rotary position embedding: the two halves of D rotate by position
    angles (``yarn`` rescales the frequencies and cos/sin).

    x:         (..., S, H, D)
    positions: (..., S) int32 - broadcastable against x's batch/seq dims.
    """
    if theta <= 0.0:
        return x
    d = x.shape[-1]
    inv_freq = rope_frequencies(d, theta, yarn)                 # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., S, D/2)
    # insert head axis: (..., S, 1, D/2)
    angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def activation_fn(name: str):
    if name in ("silu", "swish"):
        return jax.nn.silu
    if name in ("gelu", "gelu_plain"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {name!r}")


def glu_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
            wo: jax.Array, act_name: str) -> jax.Array:
    """Gated FFN (SwiGLU / GeGLU). Gate and up are separate weights so TP
    sharding of the f dim never slices across a packed boundary."""
    act = activation_fn(act_name)
    gate = jnp.einsum("...d,df->...f", x, w_gate)
    up = jnp.einsum("...d,df->...f", x, w_up)
    # bf16 accumulation on the sharded-contraction matmul: the partial
    # sums cross the TP axis (all-reduce/reduce-scatter) — keeping them in
    # the weight dtype halves the wire bytes (Megatron-style bf16 AR).
    return jnp.einsum("...f,fd->...d", act(gate) * up, wo,
                      preferred_element_type=x.dtype)


def plain_ffn(x: jax.Array, wi: jax.Array, wo: jax.Array, act_name: str) -> jax.Array:
    act = activation_fn(act_name)
    return jnp.einsum("...f,fd->...d", act(jnp.einsum("...d,df->...f", x, wi)),
                      wo, preferred_element_type=x.dtype)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: jax.Array | None = None) -> jax.Array:
    """Mean token-level cross entropy in float32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
