"""Pallas TPU decode kernel for multi-head latent attention (MLA).

An MLA layer (DeepSeek-V2, arXiv:2405.04434) caches one latent row per
token: the RMS-normed compression ``c`` (``R`` wide, ``kv_lora_rank``)
followed by the roped key ``k_pe`` (``Dr`` wide) that every head shares.
The pool is ``(L, N, bs, W)``: every layer's pages in one array, one row
a token, and the row is both key and value. ``W`` is ``R + Dr`` rounded
up to whole 128-lane tiles, the columns past ``R + Dr`` zero: XLA lays a
bf16 array's minor dim out in 128-lane tiles in HBM whatever its declared
width (576 occupies 640), and Mosaic copies only whole tiles of it.

Decode attends in the latent space ("absorbed" attention): the caller
folds each head's key up-projection into its query, so a query is
``W`` wide (``q_nope · W_UK``, ``q_pe``, zeros), its score against a
cached row is one dot product over the whole row, and the weighted sum
runs over the rows' first ``R`` columns; the caller applies each head's
value up-projection afterwards. All ``H`` heads read the same row, so a
page is fetched once for all of them and the scores of all heads are one
``(H, W) · (W, P·bs)`` product.

``mla_decode_attention``: grid ``(B,)``, rows one after another; per row
a loop over its live pages only (``0 .. pos[b] // bs``), ``P`` pages a
loop step (about ``_KEYS_PER_STEP`` positions). The pool stays in HBM
(``memory_space=pl.ANY``); the step's pages are fetched by physical id
from the scalar-prefetched table, and from the layer the scalar-prefetched
``layer`` names, by manual async copies into a two-deep VMEM buffer, the
next step's copies (or the next row's first) in flight while this step
computes. The kernel only reads the pool: the step's new rows are written
into it beforehand (``repro.models.attention``), so each query attends
its own token.

Validity comes from causality alone, as in the GQA kernels
(``repro.kernels.paged_attention``): positions past a row's query
position are masked, pages past its last live page are fetched as that
page, and a drained row (all-trash table) reads the trash page.
``repro.kernels.ref.mla_paged_attention_ref`` is the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38  # finite f32 mask value
# cache positions a loop step walks: a 640-lane bf16 latent page of 16
# tokens is 20 KiB, so 512 positions are 0.66 MB a step
_KEYS_PER_STEP = 512


def _pages_per_step(bs: int, n_blocks: int) -> int:
    return max(1, min(n_blocks, _KEYS_PER_STEP // bs))


def _kernel(
    tables_ref,
    pos_ref,
    layer_ref,
    q_ref,
    pool_hbm,
    o_ref,
    buf,
    sems,
    slot_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    P: int,
    n_blocks: int,
    bs: int,
    R: int,
    scale: float,
):
    b = pl.program_id(0)
    layer = layer_ref[0]

    def last_page(r):
        """The page holding row ``r``'s query position, or the table's
        last if that lies past the table."""
        return jnp.minimum(pos_ref[r] // bs, n_blocks - 1)

    def fetches(r, step, slot):
        """The DMAs of row ``r``'s pages ``step*P ..``: pages past its
        last live one are fetched as that one (masked, and never a page
        the row does not own)."""
        last = last_page(r)
        return [
            pltpu.make_async_copy(
                pool_hbm.at[layer, tables_ref[r, jnp.minimum(step * P + p, last)]],
                buf.at[slot, p],
                sems.at[slot],
            )
            for p in range(P)
        ]

    @pl.when(b == 0)
    def _prime():
        slot_ref[0] = 0
        for c in fetches(0, 0, 0):
            c.start()

    pos = pos_ref[b]
    s_last = last_page(b) // P
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    q = q_ref[0]  # (H, W)
    H = q.shape[0]

    def step_body(step, carry):
        slot = slot_ref[0]
        nxt = 1 - slot

        @pl.when(step < s_last)
        def _next_step():
            for c in fetches(b, step + 1, nxt):
                c.start()

        @pl.when((step == s_last) & (b + 1 < pl.num_programs(0)))
        def _next_row():
            for c in fetches(b + 1, 0, nxt):
                c.start()

        for c in fetches(b, step, slot):
            c.wait()

        rows = buf[slot].reshape(P * bs, -1)  # (P*bs, W)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (H, P*bs): every head against every row, one product
        kpos = step * (P * bs) + jax.lax.broadcasted_iota(jnp.int32, (H, P * bs), 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # the values are the rows' first R columns; probabilities in the
        # rows' dtype for the product, accumulated in f32
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(rows.dtype), rows[:, :R], preferred_element_type=jnp.float32
        )
        m_ref[...] = m_cur
        slot_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, s_last + 1, step_body, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_rank", "scale", "interpret"))
def mla_decode_attention(
    q: jax.Array,
    pool: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    layer: jax.Array,
    *,
    kv_rank: int,
    scale: float,
    interpret: bool,
) -> jax.Array:
    """Absorbed single-token MLA attention over a latent page pool.

    q: (B, H, W) absorbed queries; pool: (L, N, bs, W) latent pages of
    every layer, this step's rows already written; block_tables:
    (B, max_blocks) int32; pos: (B,) int32 query positions; layer: the
    pool layer to read (traced scalar). Returns (B, H, R) in q's dtype:
    each head's probability-weighted sum of the rows' first ``kv_rank``
    columns. ``interpret`` has no default: only a caller off the TPU asks
    for the Pallas interpreter.
    """
    B, H, W = q.shape
    _, _, bs, Wp = pool.shape
    if W != Wp or not 0 < kv_rank < W:
        raise ValueError(f"latent widths disagree: q {W}, pool {Wp}, rank {kv_rank}")
    if pos.shape != (B,):
        raise ValueError("pos must be a (B,) vector")
    n_blocks = block_tables.shape[1]
    P = _pages_per_step(bs, n_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, kv_rank), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P, bs, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),  # the buffer the next step reads
            pltpu.VMEM((H, kv_rank), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, P=P, n_blocks=n_blocks, bs=bs, R=kv_rank, scale=scale
    )
    # the grid's default "arbitrary" order runs rows one after another,
    # which the hand-off of prefetches between rows needs
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, kv_rank), q.dtype),
        interpret=interpret,
    )(
        block_tables,
        pos,
        jnp.asarray(layer, jnp.int32).reshape(1),
        q,
        pool,
    )
