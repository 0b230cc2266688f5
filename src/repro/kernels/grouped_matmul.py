"""Pallas TPU grouped (per-expert) matmul — the Expert-module hot spot.

Computes (E, C, d) x (E, d, f) -> (E, C, f): one GEMM per expert over its
capacity-dispatched token slab. This is the compute kernel behind both the
EP path (post-all_to_all slabs) and the TP path (f sharded) of
``repro.models.moe``.

TPU mapping: grid (E, C/bc, f/bf, d/bk) with the contraction axis
innermost/sequential; f32 VMEM accumulator scratch; tiles aligned to the
TPU's (8, 128) tiling or whole dims (``tile_size``). VMEM working set per step:
bc*bk + bk*bf + bc*bf floats — e.g. 128^2 * 3 * 4B = 192 KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANE, SUBLANE, pad_dim, tile_size


def _gmm_kernel(lhs_ref, rhs_ref, out_ref, acc_ref, *, n_k: int):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        lhs_ref[0].astype(jnp.float32),
        rhs_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == n_k - 1)
    def _done():
        out_ref[0, ...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bc", "bf", "bk", "interpret"))
def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    *,
    bc: int = 128,
    bf: int = 128,
    bk: int = 512,
    interpret: bool,
) -> jax.Array:
    """(E, C, d) x (E, d, f) -> (E, C, f) with f32 accumulation.

    ``interpret`` has no default: only a caller off the TPU asks for the
    Pallas interpreter."""
    E, C, d = lhs.shape
    f = rhs.shape[2]
    assert rhs.shape[:2] == (E, d)
    # aligned tiles (or whole dims); ragged per-plan shapes (capacity
    # slabs, d_ff shards) are zero-padded — zero rows and zero
    # contraction terms leave the product unchanged (see tile_size)
    bc, c_p = tile_size(C, bc, SUBLANE)
    bf, f_p = tile_size(f, bf, LANE)
    bk, d_p = tile_size(d, bk, LANE)
    lhs = pad_dim(pad_dim(lhs, 1, c_p), 2, d_p)
    rhs = pad_dim(pad_dim(rhs, 1, d_p), 2, f_p)
    n_k = d_p // bk
    grid = (E, c_p // bc, f_p // bf, n_k)

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bk), lambda e, i, j, kk: (e, i, kk)),
            pl.BlockSpec((1, bk, bf), lambda e, i, j, kk: (e, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, kk: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, c_p, f_p), lhs.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=interpret,
    )(lhs, rhs)
    return out[:, :C, :f]
