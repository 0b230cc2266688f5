"""Pallas TPU paged-attention kernels (the decode hot spot).

One fused cache-appending attention step over a block-pooled KV cache
(DESIGN.md §4b): the chunk's new K/V are scattered into their physical
pages *and* the row's logical KV view is attended with an on-chip online
softmax, in a single kernel — the pure-jnp path materializes every row's
gathered ``(B, max_blocks * block_size, Hkv, hd)`` view in HBM per step,
which these kernels never do.

Single-token decode (``C == 1``, picked from ``q.shape[1]``) has a
kernel of its own, ``_decode_kernel``: grid ``(B,)``, and per row a loop
over its live pages only (``0 .. pos[b] // block_size``), ``P`` pages a
loop step (about ``_KEYS_PER_STEP`` positions). The pool stays in HBM
(``memory_space=pl.ANY``, aliased in and out); each step's pages are
fetched by physical id from the scalar-prefetched table with manual async
copies into a two-deep VMEM buffer, the next step's copies (or the next
row's first) in flight while this step computes. Per kv head, its G q
heads are one ``(G, hd)·(hd, P·bs)`` scores product and one online
softmax update, with the probabilities kept in f32 for the value product.
The new token's K/V are written by DMA into their one slot of the pool
and patched into the step's VMEM copy, so the token is attended in the
step that writes it; no other page is written.

The chunk path (``C > 1``) and ``prefix_paged_attention`` walk pages
as follows. TPU mapping: grid ``(B, max_blocks)`` with the page axis
innermost and sequential (FlashAttention-2 carry in VMEM scratch).
Blocks are whole pages ``(1, block_size, Hkv, hd)`` and whole rows
``(1, C, Hq, hd)``, so the two minor block dims always equal the array's
(the TPU tiling rule for blocks); the kernel loops over the kv heads of a
page. The per-row
block-table walk rides the BlockSpec index maps: ``block_tables`` and
``pos`` are scalar-prefetch operands (SMEM), so each grid step DMAs
exactly the physical page ``block_tables[b, j]`` into VMEM — pages are
fetched by id, never gathered. The chunk append is fused with the
scatter: each page slot builds a one-hot selector against the chunk's
token indices (an MXU matmul, no in-kernel gather) and the page is
written back through an aliased output, so stale slots copy through
unchanged and written slots carry the new K/V into the same step's
attention.

Semantics match ``repro.kernels.ref.paged_attention_ref`` exactly:

- write positions are ``pos[b] .. pos[b] + C - 1`` per row; slots whose
  logical position falls outside that range keep their page content
  (out-of-range appends simply never land — no trash-block routing is
  needed on the kernel side),
- validity comes from causality alone: a row's stale/unwritten logical
  positions always sit *above* its query position, and all-masked pages
  self-correct under the online softmax (the finite ``NEG_INF`` mask
  value makes the rescale factor an exact zero once a valid page
  arrives),
- drained rows (all-trash tables) read whatever the trash page holds —
  finite garbage, discarded by the engine, exactly like the jnp path.

GQA: q heads are grouped over kv heads (head ``h`` serves q heads
``h*G .. (h+1)*G - 1``); the non-dividing TP head-replication case is
routed to the reference path by ``repro.kernels.ops``. Heads-sharded
plans call this kernel *per KV shard* inside a ``shard_map`` (the head
loop then counts local heads; G is preserved because q and kv heads
divide the TP axis together — ``ops.decode_attention``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38  # finite f32 mask value (see module docstring)

# Mosaic's default scoped-VMEM budget is 16 MiB; a 512-token prefill
# chunk at 16 heads x 128 needs more (double-buffered q/out/new-KV rows
# plus the f32 accumulator), so each call asks for what it needs, capped
# well inside the 128 MiB of a v5e core.
_VMEM_FLOOR = 16 * 2**20
_VMEM_CAP = 96 * 2**20
_ROWS_PER_TILE = 128  # chunk rows per step of the in-kernel query loop
# cache positions a single-token decode loop step walks: of 128, 256 and
# 512 the fastest on one v5e at Mixtral-8x7B's and DeepSeek-MoE-16B's
# decode widths (64 rows, 16-token pages, ~800 live positions a row)
_KEYS_PER_STEP = 128


def _vmem_params(block_bytes: int, scratch_bytes: int) -> pltpu.CompilerParams:
    need = 2 * block_bytes + scratch_bytes  # pipelined blocks are 2-deep
    if need > _VMEM_CAP:
        raise ValueError(
            f"paged attention needs ~{need / 2**20:.0f} MiB of VMEM "
            f"(cap {_VMEM_CAP / 2**20:.0f} MiB): use a smaller prefill chunk"
        )
    return pltpu.CompilerParams(
        vmem_limit_bytes=max(_VMEM_FLOOR, need + need // 2)
    )


def _append_attend_page(
    q_ref,
    k_page_ref,
    v_page_ref,
    k_new_ref,
    v_new_ref,
    k_out_ref,
    v_out_ref,
    acc_ref,
    m_ref,
    l_ref,
    lead: tuple,
    *,
    p0,
    j,
    is_global,
    scale: float,
    softcap: float,
    window: int,
    bs: int,
    C: int,
    tq: int,
    G: int,
    Hkv: int,
):
    """One page of one row: fused chunk append, then the online-softmax
    update of every q head, ``tq`` chunk rows at a time (a loop, not an
    unrolled body: Mosaic's compile time grows steeply with the rows a
    body handles). ``lead`` prefixes the scratch index (the prefix kernel
    keeps per-row carries)."""
    n_tiles = C // tq
    hd = k_page_ref.shape[-1]
    # masks are built 2-D from iotas: Mosaic cannot relayout a 1-D bool
    # vector into a column
    idx = j * bs - p0 + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    wmask = (idx >= 0) & (idx < C)  # (bs, 1): page slot takes a chunk token

    def appended(new_ref, h):
        """The chunk rows that land in this page's slots, (bs, hd)."""
        if C == 1:
            return new_ref[0, :, h, :].astype(jnp.float32)  # broadcasts

        # slot-side one-hot select of the chunk token that lands in each
        # slot — an MXU matmul instead of an in-kernel gather
        def tile(t, acc):
            rows = new_ref[0, pl.ds(t * tq, tq), h, :].astype(jnp.float32)
            sel = idx - t * tq == jax.lax.broadcasted_iota(jnp.int32, (bs, tq), 1)
            return acc + jnp.dot(
                sel.astype(jnp.float32), rows, preferred_element_type=jnp.float32
            )

        return jax.lax.fori_loop(0, n_tiles, tile, jnp.zeros((bs, hd), jnp.float32))

    def attend(qh, k_page, v_page):
        def tile(t, carry):
            q = q_ref[0, pl.ds(t * tq, tq), qh, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k_page, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (tq, bs)
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 1)
            qpos = p0 + t * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 0)
            ok = kpos <= qpos  # causal — also kills stale slots
            if window > 0:
                ok = ok & (((qpos - kpos) < window) | is_global)
            s = jnp.where(ok, s, NEG_INF)

            at = lead + (qh, t)
            m_prev = m_ref[at]  # (tq, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_ref[at] = l_ref[at] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[at] = acc_ref[at] * alpha + jnp.dot(
                p, v_page, preferred_element_type=jnp.float32
            )
            m_ref[at] = m_cur
            return carry

        jax.lax.fori_loop(0, n_tiles, tile, 0)

    for h in range(Hkv):
        k_page = k_page_ref[0, :, h, :].astype(jnp.float32)  # (bs, hd)
        v_page = v_page_ref[0, :, h, :].astype(jnp.float32)
        k_page = jnp.where(wmask, appended(k_new_ref, h), k_page)
        v_page = jnp.where(wmask, appended(v_new_ref, h), v_page)
        # unconditional write-back: the aliased out buffer holds a
        # *different* page from the previous grid step, so copying through
        # is load-bearing
        k_out_ref[0, :, h, :] = k_page.astype(k_out_ref.dtype)
        v_out_ref[0, :, h, :] = v_page.astype(v_out_ref.dtype)
        for g in range(G):
            attend(h * G + g, k_page, v_page)


def _write_out(o_ref, acc_ref, l_ref, lead: tuple, tq: int):
    C, Hq = o_ref.shape[1], o_ref.shape[2]
    for qh in range(Hq):

        def tile(t, carry, qh=qh):
            at = lead + (qh, t)
            out = acc_ref[at] / jnp.maximum(l_ref[at], 1e-30)
            o_ref[0, pl.ds(t * tq, tq), qh, :] = out.astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, C // tq, tile, 0)


def _paged_kernel(
    tables_ref,
    pos_ref,
    flags_ref,
    q_ref,
    k_page_ref,
    v_page_ref,
    k_new_ref,
    v_new_ref,
    o_ref,
    k_out_ref,
    v_out_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    n_blocks: int,
    **static,
):
    b = pl.program_id(0)
    j = pl.program_id(1)  # page walk: innermost, sequential

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    _append_attend_page(
        q_ref, k_page_ref, v_page_ref, k_new_ref, v_new_ref, k_out_ref,
        v_out_ref, acc_ref, m_ref, l_ref, (),
        p0=pos_ref[b], j=j, is_global=flags_ref[0] != 0, **static,
    )

    @pl.when(j == n_blocks - 1)
    def _finalize():
        _write_out(o_ref, acc_ref, l_ref, (), static["tq"])


def _prefix_kernel(
    tables_ref,
    pos_ref,
    flags_ref,
    reps_ref,
    nsh_ref,
    q_ref,
    k_page_ref,
    v_page_ref,
    k_new_ref,
    v_new_ref,
    o_ref,
    k_out_ref,
    v_out_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    n_blocks: int,
    **static,
):
    """Prefix-group variant of ``_paged_kernel``: grid (n_blocks, B) with
    the *row* axis innermost, so consecutive rows of one prefix group hit
    the same physical page at a shared ``j`` — the page BlockSpec
    resolves to the group representative's table entry there, and
    Pallas's revisit elision skips the re-DMA (the shared block is walked
    once per group, not once per row). Per-row online-softmax carries
    live in row-indexed VMEM scratch since the row axis is no longer
    outermost."""
    j = pl.program_id(0)  # page walk: sequential, but no longer innermost
    b = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[b] = jnp.zeros_like(acc_ref[b])
        m_ref[b] = jnp.full_like(m_ref[b], NEG_INF)
        l_ref[b] = jnp.zeros_like(l_ref[b])

    # writes only ever land in exclusively-owned pages (pos[b] >=
    # shared_blocks[b] * bs: COW ran before the step), so shared pages
    # always copy through unchanged
    _append_attend_page(
        q_ref, k_page_ref, v_page_ref, k_new_ref, v_new_ref, k_out_ref,
        v_out_ref, acc_ref, m_ref, l_ref, (b,),
        p0=pos_ref[b], j=j, is_global=flags_ref[0] != 0, **static,
    )

    @pl.when(j == n_blocks - 1)
    def _finalize():
        _write_out(o_ref, acc_ref, l_ref, (b,), static["tq"])


def _paged_call(kernel, index_maps, n_prefetch, grid, n_blocks, lead, q,
                k_pages, v_pages, k_new, v_new, scale, softcap, window,
                interpret):
    """Shared ``pallas_call`` plumbing: whole-page / whole-row blocks,
    per-q-head f32 carries (``lead`` extra leading scratch dims), pages
    aliased to the page outputs."""
    B, C, Hq, hd = q.shape
    bs, Hkv = k_pages.shape[1], k_pages.shape[2]
    if Hq % Hkv:
        raise ValueError("GQA requires q heads to divide over kv heads")
    page_map, row_map = index_maps
    page_spec = pl.BlockSpec((1, bs, Hkv, hd), page_map)
    q_spec = pl.BlockSpec((1, C, Hq, hd), row_map)
    kv_spec = pl.BlockSpec((1, C, Hkv, hd), row_map)
    tq = max(t for t in range(1, min(C, _ROWS_PER_TILE) + 1) if C % t == 0)
    carry = lead + (Hq, C // tq, tq)
    scratch = [
        pltpu.VMEM(carry + (hd,), jnp.float32),
        pltpu.VMEM(carry + (1,), jnp.float32),
        pltpu.VMEM(carry + (1,), jnp.float32),
    ]
    block_bytes = (
        2 * C * Hq * hd * q.dtype.itemsize  # q in, out
        + 2 * C * Hkv * hd * k_new.dtype.itemsize  # new K/V rows
        + 4 * bs * Hkv * hd * k_pages.dtype.itemsize  # pages in + out
    )
    # every (tq, hd) / (tq, 1) carry occupies whole (8, 128) f32 tiles
    scratch_bytes = 4 * math.prod(carry[:-1]) * -(-tq // 8) * 8 * (hd + 2 * 128)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=grid,
        in_specs=[q_spec, page_spec, page_spec, kv_spec, kv_spec],
        out_specs=[q_spec, page_spec, page_spec],
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        kernel,
        scale=hd**-0.5 if scale is None else scale,
        softcap=softcap,
        window=window,
        bs=bs,
        C=C,
        tq=tq,
        G=Hq // Hkv,
        Hkv=Hkv,
        n_blocks=n_blocks,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # operand indices count the scalar-prefetch args: pages -> page outs
        input_output_aliases={n_prefetch + 1: 1, n_prefetch + 2: 2},
        compiler_params=_vmem_params(block_bytes, scratch_bytes),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "window", "interpret"))
def prefix_paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pos: jax.Array,
    group_reps: jax.Array,
    shared_blocks: jax.Array,
    is_global=True,
    *,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    window: int = 0,
    interpret: bool,
):
    """Prefix-group fused paged append + decode attention.

    Same contract as ``paged_attention`` plus two (B,) scalar-prefetch
    operands: ``group_reps[b]`` is row ``b``'s prefix-group representative
    and ``shared_blocks[b]`` the number of leading block-table entries it
    shares with that rep (identical physical ids — the engine contract,
    DESIGN.md §4d). Shared entries are fetched through the rep's table
    row; with the row axis innermost in the grid, every row of a group
    revisits the rep's physical page at shared ``j`` and the page DMA is
    elided after the first row. Token-exact vs ``paged_attention`` on the
    rows' own tables (``ref.prefix_paged_attention_ref`` is the oracle).
    The per-row carries are ``B`` times those of ``paged_attention``: this
    kernel serves decode steps, not long prefill chunks.
    """
    B = q.shape[0]
    if pos.shape != (B,):
        raise ValueError("pos must be a (B,) vector (broadcast scalars)")
    if group_reps.shape != (B,) or shared_blocks.shape != (B,):
        raise ValueError("group_reps / shared_blocks must be (B,) vectors")
    n_blocks = block_tables.shape[1]
    flags = jnp.asarray(is_global, jnp.int32).reshape(1)

    def page_map(j, b, tables, pos, flags, reps, nsh):
        row = jnp.where(j < nsh[b], reps[b], b)
        return (tables[row, j], 0, 0, 0)

    def row_map(j, b, *_):
        return (b, 0, 0, 0)

    call = _paged_call(
        _prefix_kernel, (page_map, row_map), 5, (n_blocks, B), n_blocks, (B,), q,
        k_pages, v_pages, k_new, v_new, scale, softcap, window, interpret,
    )
    return call(
        block_tables,
        pos,
        flags,
        group_reps,
        shared_blocks,
        q,
        k_pages,
        v_pages,
        k_new,
        v_new,
    )


def _decode_kernel(
    tables_ref,
    pos_ref,
    flags_ref,
    q_ref,
    k_hbm,
    v_hbm,
    k_new_ref,
    v_new_ref,
    o_ref,
    k_out,
    v_out,
    k_buf,
    v_buf,
    sems,
    slot_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    P: int,
    n_blocks: int,
    bs: int,
    G: int,
    Hkv: int,
    scale: float,
    softcap: float,
    window: int,
):
    """One row of a single-token decode step: its live pages only, ``P``
    pages a loop step, fetched by id from the HBM pool into a two-deep
    VMEM buffer (the next step's pages, or the next row's first, are in
    flight while this step computes), one scores dot per kv head over
    all ``G`` of its q heads, and the new token written into its one
    page."""
    b = pl.program_id(0)
    is_global = flags_ref[0] != 0

    def last_page(r):
        """The last page row ``r`` attends: the one holding its query
        position, or the table's last if that lies past the table."""
        return jnp.minimum(pos_ref[r] // bs, n_blocks - 1)

    def first_step(r):
        """Steps wholly before a sliding window hold nothing row ``r``
        attends (the last live step always runs)."""
        if window <= 0:
            return 0
        lo = jnp.minimum(jnp.maximum(pos_ref[r] - window + 1, 0) // bs, last_page(r))
        return jnp.where(is_global, 0, lo // P)

    def fetches(r, step, slot):
        """The DMAs of row ``r``'s pages ``step*P ..``: pages past its
        last live one are fetched as that one (finite, masked, and never
        a page the row does not own)."""
        last = last_page(r)
        out = []
        for p in range(P):
            page = tables_ref[r, jnp.minimum(step * P + p, last)]
            for i, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                dst = buf.at[slot, p]
                out.append(pltpu.make_async_copy(hbm.at[page], dst, sems.at[i, slot]))
        return out

    @pl.when(b == 0)
    def _prime():
        slot_ref[0] = 0
        for c in fetches(0, first_step(0), 0):
            c.start()

    pos = pos_ref[b]
    last = last_page(b)
    s_last = last // P
    # the append: the new token's K/V go straight into their one slot of
    # the pool; a position past the table lands nowhere
    lands = pos // bs < n_blocks
    page = tables_ref[b, last]
    off = pos % bs
    writes = [
        pltpu.make_async_copy(new.at[0], out.at[page, pl.ds(off, 1)], sems.at[2, i])
        for i, (new, out) in enumerate(((k_new_ref, k_out), (v_new_ref, v_out)))
    ]

    @pl.when(lands)
    def _write():
        for c in writes:
            c.start()

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def step_body(step, carry):
        slot = slot_ref[0]
        nxt = 1 - slot

        @pl.when(step < s_last)
        def _next_step():
            for c in fetches(b, step + 1, nxt):
                c.start()

        @pl.when((step == s_last) & (b + 1 < pl.num_programs(0)))
        def _next_row():
            for c in fetches(b + 1, first_step(b + 1), nxt):
                c.start()

        for c in fetches(b, step, slot):
            c.wait()

        # this step's own copy of the new token, whatever the fetch read
        @pl.when(lands & (step == s_last))
        def _merge():
            k_buf[slot, last - step * P, off] = k_new_ref[0, 0]
            v_buf[slot, last - step * P, off] = v_new_ref[0, 0]

        kpos = step * (P * bs) + jax.lax.broadcasted_iota(jnp.int32, (G, P * bs), 1)
        ok = kpos <= pos
        if window > 0:
            ok = ok & (((pos - kpos) < window) | is_global)
        for h in range(Hkv):
            q = q_ref[0, 0, pl.ds(h * G, G), :]  # (G, hd)
            k = k_buf[slot, :, :, h, :].reshape(P * bs, -1)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (G, P*bs)
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[h]  # (G, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            v = v_buf[slot, :, :, h, :].reshape(P * bs, -1).astype(jnp.float32)
            # HIGHEST: at the default precision Mosaic rounds f32 operands
            # to bf16 on the MXU, which would round the probabilities
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_cur
        slot_ref[0] = nxt
        return carry

    jax.lax.fori_loop(first_step(b), s_last + 1, step_body, 0)
    for h in range(Hkv):
        out = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
        o_ref[0, 0, pl.ds(h * G, G), :] = out.astype(o_ref.dtype)

    @pl.when(lands)
    def _written():
        for c in writes:
            c.wait()


def _decode_call(q, k_pages, n_blocks, scale, softcap, window, interpret):
    """``pallas_call`` of the single-token kernel: grid ``(B,)``, the pool
    left in HBM (aliased in and out), a row's q / new K/V / output as
    whole-row blocks, ``P`` pages a step from the shapes."""
    B, _, Hq, hd = q.shape
    bs, Hkv = k_pages.shape[1], k_pages.shape[2]
    if Hq % Hkv:
        raise ValueError("GQA requires q heads to divide over kv heads")
    G = Hq // Hkv
    P = _pages_per_step(bs, n_blocks)
    q_spec = pl.BlockSpec((1, 1, Hq, hd), lambda b, *_: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, Hkv, hd), lambda b, *_: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    item = k_pages.dtype.itemsize

    def rows(n):  # rows of whole VMEM tiles: 8 f32 or 16 bf16 rows a tile
        return -(-n // (32 // item)) * (32 // item)

    block_bytes = 2 * rows(Hq) * hd * q.dtype.itemsize + 2 * rows(Hkv) * hd * item
    scratch_bytes = (
        4 * P * bs * rows(Hkv) * hd * item  # K and V, two steps deep
        + 2 * P * bs * hd * 4  # one head's f32 values and their scores
        + 4 * Hkv * -(-G // 8) * 8 * (hd + 2 * 128)  # f32 carries, whole tiles
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[q_spec, pool_spec, pool_spec, kv_spec, kv_spec],
        out_specs=[q_spec, pool_spec, pool_spec],
        scratch_shapes=[
            pltpu.VMEM((2, P, bs, Hkv, hd), k_pages.dtype),
            pltpu.VMEM((2, P, bs, Hkv, hd), k_pages.dtype),
            pltpu.SemaphoreType.DMA((3, 2)),  # K fetch, V fetch, append
            pltpu.SMEM((1,), jnp.int32),  # the buffer the next step reads
            pltpu.VMEM((Hkv, G, hd), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        P=P,
        n_blocks=n_blocks,
        bs=bs,
        G=G,
        Hkv=Hkv,
        scale=hd**-0.5 if scale is None else scale,
        softcap=softcap,
        window=window,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
        ],
        input_output_aliases={4: 1, 5: 2},  # pools, counting the 3 prefetched
        # the grid's default "arbitrary" order runs rows one after another,
        # which the hand-off of buffers and prefetches between rows needs
        compiler_params=_vmem_params(block_bytes, scratch_bytes),
        interpret=interpret,
    )


def _pages_per_step(bs: int, n_blocks: int) -> int:
    """Pages a loop step walks: about ``_KEYS_PER_STEP`` keys, never more
    pages than the table holds (one whole page where a page alone is
    that long, as in the contiguous layout's one page a row)."""
    return max(1, min(n_blocks, _KEYS_PER_STEP // bs))


def _slices_pages(Hkv: int, itemsize: int) -> bool:
    """Whether Mosaic can slice one page out of the pool in HBM. XLA tiles
    a packed (sub-32-bit) pool's kv-head dim by the power of two at or
    above it, at least one 32-bit word and at most 8 rows deep, and Mosaic
    slices only whole tiles (compiles for a described v5e: bf16 pools with
    2, 4, 8, 16, 24 or 32 kv heads slice, 1, 3, 5, 6 or 12 do not). Pools
    that do not take the page-walk kernel of the chunk path instead."""
    if itemsize >= 4:
        return True
    tile = min(8, max(4 // itemsize, 1 << (Hkv - 1).bit_length()))
    return Hkv % tile == 0


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "window", "interpret"))
def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pos: jax.Array,
    is_global=True,
    *,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    window: int = 0,
    interpret: bool,
):
    """Fused paged append + decode attention.

    q: (B, C, Hq, hd) rope'd queries; k_pages/v_pages: (N, bs, Hkv, hd)
    shared physical pages; block_tables: (B, max_blocks) int32;
    k_new/v_new: (B, C, Hkv, hd) rope'd chunk K/V; pos: (B,) int32 write
    positions; ``is_global`` may be traced (per-layer sliding-window
    flag). Returns ``(out (B, C, Hq, hd), k_pages, v_pages)`` with the
    pages updated in place (aliased). ``interpret`` has no default: only
    a caller off the TPU asks for the Pallas interpreter.
    """
    B = q.shape[0]
    if pos.shape != (B,):
        raise ValueError("pos must be a (B,) vector (broadcast scalars)")
    n_blocks = block_tables.shape[1]
    flags = jnp.asarray(is_global, jnp.int32).reshape(1)
    if q.shape[1] == 1 and _slices_pages(k_pages.shape[2], k_pages.dtype.itemsize):
        call = _decode_call(q, k_pages, n_blocks, scale, softcap, window, interpret)
        return call(
            block_tables, pos, flags, q, k_pages, v_pages,
            k_new.astype(k_pages.dtype), v_new.astype(v_pages.dtype),
        )

    def page_map(b, j, tables, pos, flags):
        return (tables[b, j], 0, 0, 0)

    def row_map(b, j, *_):
        return (b, 0, 0, 0)

    call = _paged_call(
        _paged_kernel, (page_map, row_map), 3, (B, n_blocks), n_blocks, (), q,
        k_pages, v_pages, k_new, v_new, scale, softcap, window, interpret,
    )
    return call(block_tables, pos, flags, q, k_pages, v_pages, k_new, v_new)
