"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors its kernel's exact contract; the kernel tests sweep
shapes/dtypes and assert_allclose against these. The decode-attention
references double as the ``ref`` kernel backend the models execute
off-TPU (``repro.kernels.ops``): their math is the single-chunk online
softmax the model layer used inline before the kernel seam existed, so
greedy outputs are unchanged by the dispatch refactor.
``decode_attend_ref`` additionally serves the prefill-flash seam's
``ref`` path (``ops.flash_attention`` over arange positions — it is the
only oracle that takes the traced per-layer ``is_global`` flag), and
``grouped_matmul_ref``/``int4_dequant_ref`` the expert-FFN seam,
including the INT4 ``QuantizedWeight`` dequant-then-matmul path. Under
sharded plans these references are what XLA partitions when a plan
cannot map onto the shard_map'ed kernels (DESIGN.md §4c).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def flash_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Naive attention. q: (B, Hq, Sq, hd); k/v: (B, Hkv, Sk, hd).

    GQA: q heads grouped over kv heads (Hq % Hkv == 0). ``window`` > 0
    restricts to a sliding window; ``kv_len`` masks positions >= kv_len
    (decode). Query positions are aligned to the END of the kv sequence
    when Sq != Sk (decode semantics: q_pos = Sk - Sq + i, or kv_len - Sq + i
    when kv_len is given).
    """
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = hd**-0.5
    qf = q.reshape(B, Hkv, G, Sq, hd).astype(jnp.float32)
    logits = jnp.einsum("bkgqd,bksd->bkgqs", qf, k.astype(jnp.float32)) * scale
    if softcap > 0:
        logits = softcap_ref(logits, softcap)
    kpos = jnp.arange(Sk)
    if kv_len is not None:
        qpos = kv_len - Sq + jnp.arange(Sq)
    else:
        qpos = Sk - Sq + jnp.arange(Sq)
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok = kpos[None, :] <= qpos[:, None]
        if window > 0:
            ok &= (qpos[:, None] - kpos[None, :]) < window
    if kv_len is not None:
        ok &= kpos[None, :] < kv_len
    logits = jnp.where(ok[None, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, Sq, hd).astype(q.dtype)


def softcap_ref(x, cap):
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# decode attention (contiguous + paged cache-appending steps)
# ---------------------------------------------------------------------------
def _decode_mask_bias(
    q_pos: jax.Array,
    k_pos: jax.Array,
    window: int,
    is_global,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Additive causal decode mask in f32: (Sq, Sk) or (B, Sq, Sk) per-row.

    ``q_pos`` is (Sq,) shared or (B, Sq) per-row; ``kv_len`` a scalar or
    (B,) valid-length bound; ``is_global`` (may be traced) disables the
    sliding window for global layers.
    """
    qp = q_pos[..., :, None]  # (..., Sq, 1)
    ok = k_pos <= qp
    if window > 0:
        win_ok = ok & ((qp - k_pos) < window)
        ok = jnp.where(is_global, ok, win_ok)
    if kv_len is not None:
        kl = jnp.asarray(kv_len)
        if kl.ndim:
            kl = kl[:, None, None]  # (B, 1, 1)
        ok = ok & (k_pos < kl)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def decode_attend_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,
    k_positions: jax.Array,
    *,
    scale: float,
    softcap: float = 0.0,
    window: int = 0,
    is_global=True,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Single-chunk masked attention over a full decode cache.

    q (B, Sq, Hq, hd), k/v (B, Sk, Hkv, hd) -> (B, Sq, Hq, hd). GQA via
    head grouping; masked positions contribute exact zeros after the
    max-subtracted softmax (the chunked-prefill equivalence contract,
    DESIGN.md §4b).
    """
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    bias = _decode_mask_bias(q_positions, k_positions, window, is_global, kv_len)
    qg = q.reshape(B, Sq, Hkv, G, hd)
    logits = (
        jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32))
        * scale
    )
    if softcap > 0:
        logits = softcap_ref(logits, softcap)
    logits = logits + (
        bias[None, None, None, :, :] if bias.ndim == 2 else bias[:, None, None, :, :]
    )
    m = jnp.max(logits, axis=-1)  # (B,Hkv,G,Sq)
    p = jnp.exp(logits - m[..., None])
    s = jnp.sum(p, axis=-1)
    # probabilities in the value dtype for the AV matmul (p in [0,1] is
    # safe in bf16; the normalizer s stays f32) — matches the model's
    # prefill math bit-for-bit, which the greedy-equivalence tests rely on
    o = jnp.einsum(
        "bkgqs,bskd->bkgqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    out = o / jnp.maximum(s[..., None], 1e-30)
    out = out.reshape(B, Hkv, G, Sq, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, Sq, Hq, hd).astype(q.dtype)


def _chunk_positions(pos: jax.Array, C: int) -> jax.Array:
    """Write/query positions for a C-token append: (B, C) or (1, C)."""
    return (pos[:, None] if pos.ndim else pos[None, None]) + jnp.arange(
        C, dtype=jnp.int32
    )


def paged_attention_ref(
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
    trash_block: int = 0,
    repeat_kv: int = 1,
    constrain: Optional[Callable[[jax.Array], jax.Array]] = None,
):
    """Fused paged append + decode attention (the paged-kernel oracle).

    Contract of ``repro.kernels.paged_attention.paged_attention``: scatter
    the chunk's K/V through each row's block table (positions past the
    table width land in ``trash_block``, never in a live page), gather
    every row's logical view and attend with causality as the only
    validity mask (stale gathered positions always sit above the query
    position). Extras the jnp path supports beyond the kernel: a
    ``constrain`` sharding callback applied to the scattered pages and
    ``repeat_kv`` head replication of the gathered view (the non-dividing
    TP case) — ``repro.kernels.ops`` routes those here.
    """
    B, C = q.shape[0], q.shape[1]
    bs = k_pages.shape[1]
    max_blocks = block_tables.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    q_pos = _chunk_positions(pos, C)
    tpos = jnp.broadcast_to(q_pos, (B, C))  # write positions
    blk = tpos // bs
    off = tpos % bs
    phys = jnp.take_along_axis(block_tables, jnp.clip(blk, 0, max_blocks - 1), axis=1)
    phys = jnp.where(blk < max_blocks, phys, trash_block)  # (B, C)
    k_pages = k_pages.at[phys, off].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[phys, off].set(v_new.astype(v_pages.dtype))
    if constrain is not None:
        k_pages = constrain(k_pages)
        v_pages = constrain(v_pages)
    # gather each row's logical view: (B, max_blocks*bs, Hkv, hd)
    k = k_pages[block_tables].reshape((B, max_blocks * bs) + k_pages.shape[2:])
    v = v_pages[block_tables].reshape((B, max_blocks * bs) + v_pages.shape[2:])
    if repeat_kv > 1:
        k = jnp.repeat(k, repeat_kv, axis=2)
        v = jnp.repeat(v, repeat_kv, axis=2)
    k_positions = jnp.arange(max_blocks * bs, dtype=jnp.int32)
    out = decode_attend_ref(
        q,
        k.astype(q.dtype),
        v.astype(q.dtype),
        q_pos if pos.ndim else q_pos[0],
        k_positions,
        scale=scale,
        softcap=softcap,
        window=window,
        is_global=is_global,
    )
    return out, k_pages, v_pages


def prefix_paged_attention_ref(
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
    trash_block: int = 0,
    repeat_kv: int = 1,
    constrain: Optional[Callable[[jax.Array], jax.Array]] = None,
):
    """Prefix-group paged attention (the prefix-aware kernel's oracle).

    ``group_reps`` (B,) names each row's prefix-group representative (a
    live row index; a row with no shared prefix is its own rep) and
    ``shared_blocks`` (B,) how many leading block-table entries the row
    shares with that representative. The engine guarantees the contract
    (DESIGN.md §4d): within the shared range the member's own table holds
    the *same* physical ids as the rep's, and every write position sits
    at or past the shared region (copy-on-write runs before the step).
    The oracle therefore routes shared entries through the rep's table —
    exactly what the Pallas kernel's group-id scalar-prefetch operand
    does so consecutive group rows revisit one physical page — and
    defers the rest to ``paged_attention_ref``, making the two paths
    token-exact by construction.
    """
    j = jnp.arange(block_tables.shape[1], dtype=jnp.int32)[None, :]
    eff = jnp.where(
        j < shared_blocks[:, None], block_tables[group_reps], block_tables
    )
    return paged_attention_ref(
        q,
        k_pages,
        v_pages,
        eff,
        k_new,
        v_new,
        pos,
        is_global,
        scale=scale,
        softcap=softcap,
        window=window,
        trash_block=trash_block,
        repeat_kv=repeat_kv,
        constrain=constrain,
    )


def append_attention_ref(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pos: jax.Array,
    is_global=True,
    *,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    window: int = 0,
    constrain: Optional[Callable[[jax.Array], jax.Array]] = None,
):
    """Contiguous-cache append + decode attention.

    k_cache/v_cache: (B, Smax, Hkv, hd). Scalar ``pos`` writes the chunk
    in lockstep at one offset; a (B,) ``pos`` scatters each row's single
    token at its own depth (rows whose pos is out of range write
    nowhere). Attention runs over the full cache with a ``pos + C``
    validity bound.
    """
    B, C = q.shape[0], q.shape[1]
    if C > 1:
        assert pos.ndim == 0, "contiguous multi-token append is lockstep-only"
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if pos.ndim:
        # per-row scatter: row i writes its token's K/V at pos[i]
        write = (
            jnp.arange(k_cache.shape[1], dtype=jnp.int32)[None, :] == pos[:, None]
        )  # (B, Smax)
        k_cache = jnp.where(
            write[:, :, None, None], k_new.astype(k_cache.dtype), k_cache
        )
        v_cache = jnp.where(
            write[:, :, None, None], v_new.astype(v_cache.dtype), v_cache
        )
    else:
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_new.astype(k_cache.dtype), (0, pos, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_new.astype(v_cache.dtype), (0, pos, 0, 0)
        )
    if constrain is not None:
        k_cache = constrain(k_cache)
        v_cache = constrain(v_cache)
    Smax = k_cache.shape[1]
    q_pos = _chunk_positions(pos, C)
    out = decode_attend_ref(
        q,
        k_cache.astype(q.dtype),
        v_cache.astype(q.dtype),
        q_pos if pos.ndim else q_pos[0],
        jnp.arange(Smax, dtype=jnp.int32),
        scale=scale,
        softcap=softcap,
        window=window,
        is_global=is_global,
        kv_len=pos + C,
    )
    return out, k_cache, v_cache


def mla_paged_attention_ref(
    q: jax.Array,
    pool: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    layer,
    *,
    kv_rank: int,
    scale: float,
) -> jax.Array:
    """Absorbed MLA decode attention (the ``mla_decode_attention`` oracle).

    q: (B, H, W) absorbed queries; pool: (L, N, bs, W) latent pages, this
    step's rows already written; each row attends the positions
    ``0 .. pos[b]`` of its logical view (causality is the only validity
    mask). Scores are q against the whole latent row, values the rows'
    first ``kv_rank`` columns. Returns (B, H, kv_rank) in q's dtype.
    """
    B, max_blocks = block_tables.shape
    bs = pool.shape[2]
    rows = pool[layer, block_tables].reshape(B, max_blocks * bs, -1)
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32), rows) * scale
    ok = jnp.arange(max_blocks * bs)[None, None, :] <= pos[:, None, None]
    p = jax.nn.softmax(jnp.where(ok, s, NEG_INF), axis=-1)
    out = jnp.einsum("bht,btr->bhr", p, rows[..., :kv_rank])
    return out.astype(q.dtype)


def grouped_matmul_ref(lhs: jax.Array, rhs: jax.Array) -> jax.Array:
    """(E, C, d) x (E, d, f) -> (E, C, f), f32 accumulation."""
    out = jnp.einsum("ecd,edf->ecf", lhs.astype(jnp.float32), rhs.astype(jnp.float32))
    return out.astype(lhs.dtype)


def int4_dequant_ref(
    packed: jax.Array, scales: jax.Array, zeros: jax.Array, out_dtype=jnp.bfloat16
) -> jax.Array:
    """Unpack + dequantize per-group INT4.

    packed: (G, gs // 2) uint8, two nibbles per byte (low nibble first).
    scales/zeros: (G, 1) float32. Output: (G, gs) = scales * q + zeros.
    """
    low = (packed & 0xF).astype(jnp.float32)
    high = (packed >> 4).astype(jnp.float32)
    vals = jnp.stack([low, high], axis=-1).reshape(packed.shape[0], -1)
    return (vals * scales + zeros).astype(out_dtype)
