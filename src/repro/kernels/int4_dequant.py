"""Pallas TPU per-group INT4 dequantization — the HAP transition hot spot.

The dynamic parallelism transition (paper §III-D, Eq. 6) keeps an INT4
per-group quantized backup of the expert weights in host memory; switching
the Expert module's parallel strategy between prefill and decode uploads
the packed nibbles and dequantizes on-device. T_dequant in the C_ij cost
matrix is the runtime of THIS kernel.

Layout: packed (G, gs/2) uint8 — two nibbles per byte, low nibble first —
plus per-group f32 scales/zeros (G, 1). Output (G, gs):
``w = scale * q + zero`` with q in [0, 15].

TPU mapping: grid over group blocks; each step unpacks a (bg, gs/2) byte
tile in VMEM into a (bg, gs) bf16 tile. Unpacking is VPU bit-twiddling
(shift/mask) + an interleaving reshape; lane dim stays 128-aligned for
gs >= 256.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import SUBLANE, pad_dim, tile_size


def _dequant_kernel(packed_ref, scale_ref, zero_ref, out_ref):
    # widen before the bit twiddling: the TPU has no uint8 -> f32 cast
    packed = packed_ref[...].astype(jnp.int32)
    low = (packed & 0xF).astype(jnp.float32)
    high = (packed >> 4).astype(jnp.float32)
    bg, half = packed.shape
    vals = jnp.stack([low, high], axis=-1).reshape(bg, 2 * half)
    out = vals * scale_ref[...] + zero_ref[...]
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "bg", "interpret"))
def int4_dequant(
    packed: jax.Array,
    scales: jax.Array,
    zeros: jax.Array,
    *,
    out_dtype=jnp.bfloat16,
    bg: int = 256,
    interpret: bool,
) -> jax.Array:
    """packed (G, gs/2) uint8 + scales/zeros (G, 1) -> (G, gs) out_dtype.

    ``interpret`` has no default: only a caller off the TPU asks for the
    Pallas interpreter."""
    G, half = packed.shape
    gs = 2 * half
    bg, g_p = tile_size(G, bg, SUBLANE)
    packed, scales, zeros = (pad_dim(a, 0, g_p) for a in (packed, scales, zeros))
    grid = (g_p // bg,)

    out = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bg, half), lambda i: (i, 0)),
            pl.BlockSpec((bg, 1), lambda i: (i, 0)),
            pl.BlockSpec((bg, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bg, gs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g_p, gs), out_dtype),
        interpret=interpret,
    )(packed, scales, zeros)
    return out[:G]
