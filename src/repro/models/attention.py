"""Attention: GQA with RoPE, sliding-window / global masks, logit
softcapping, chunked (flash-style) prefill and single-token decode.

Memory discipline: full (S, S) score tensors are never materialized for
long sequences — the prefill path scans over query chunks with an online
softmax over KV chunks (pure-jnp flash; the Pallas kernel in
``repro.kernels.flash_attention`` is the TPU-target version of the same
algorithm and is validated against ``repro.kernels.ref``).

The decode hot path is a *dispatch*: ``decode_attention`` projects
q/k/v, then hands the cache-appending attention step — contiguous or
paged layout — to ``repro.kernels.ops.decode_attention``, where a
``KernelBackend`` selects the pure-jnp reference or the Pallas
paged-attention kernel (DESIGN.md §Kernel backends).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from .common import Yarn, apply_rope, rms_norm, softcap, yarn_mscale

NEG_INF = -2.0e38  # f32-safe mask value
# reserved paged-cache block id — mirrors repro.serving.kv_cache.TRASH_BLOCK
# (kept literal here so the model layer stays import-free of serving)
TRASH_BLOCK = 0


class AttnTemps(NamedTuple):
    """Per-layer attention weights, already unstacked (no leading L)."""
    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array


def _scale(cfg: ModelConfig) -> float:
    if cfg.query_pre_attn_scalar > 0:
        return cfg.query_pre_attn_scalar ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        return cfg.head_dim ** -0.5 * yarn_mscale(cfg.yarn_factor,
                                                  cfg.yarn_mscale_all_dim) ** 2
    return cfg.head_dim ** -0.5


def _yarn(cfg: ModelConfig) -> Optional[Yarn]:
    if not cfg.yarn_factor:
        return None
    return Yarn(cfg.yarn_factor, cfg.yarn_original_max_position,
                cfg.yarn_beta_fast, cfg.yarn_beta_slow, cfg.yarn_mscale,
                cfg.yarn_mscale_all_dim)


def qkv_project(x: jax.Array, w: AttnTemps, cfg: ModelConfig,
                positions: jax.Array):
    """x: (B, S, d) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd), rope applied."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, w.wq).reshape(
        B, S, cfg.num_heads, cfg.head_dim)
    k = jnp.einsum("bsd,de->bse", x, w.wk).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim)
    v = jnp.einsum("bsd,de->bse", x, w.wv).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, cfg: ModelConfig,
               is_global, kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Additive mask bias in f32: (Sq, Sk), or (B, Sq, Sk) per-row.

    - causal models: k_pos <= q_pos
    - sliding window (when ``is_global`` is False): q_pos - k_pos < window
    - encoder-only (cfg.causal False): full bidirectional
    - kv_len: valid-length bound for decode (k_pos < kv_len)

    ``q_pos`` is (Sq,) shared across the batch, or (B, Sq) per-row — the
    continuous-batching decode path, where in-flight requests sit at
    different depths. ``kv_len`` is likewise a scalar or (B,).
    """
    qp = q_pos[..., :, None]                        # (..., Sq, 1)
    ok = jnp.ones(qp.shape[:-1] + k_pos.shape, dtype=bool)
    if cfg.causal:
        ok = k_pos <= qp
        if cfg.sliding_window > 0:
            in_win = (qp - k_pos) < cfg.sliding_window
            win_ok = ok & in_win
            ok = jnp.where(is_global, ok, win_ok)
    if kv_len is not None:
        kl = jnp.asarray(kv_len)
        if kl.ndim:
            kl = kl[:, None, None]                  # (B, 1, 1)
        ok = ok & (k_pos < kl)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa_chunk(q, k, v, bias, cfg: ModelConfig):
    """q (B,Sq,Hq,hd), k/v (B,Sk,Hkv,hd), bias (Sq,Sk) or (B,Sq,Sk)
    -> (out, row_max, row_sum).

    GQA: q heads grouped over kv heads. Returns unnormalized output plus the
    online-softmax statistics so callers can combine across KV chunks.
    """
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * _scale(cfg)
    if cfg.attn_logit_softcap > 0:
        logits = softcap(logits, cfg.attn_logit_softcap)
    logits = logits + (bias[None, None, None, :, :] if bias.ndim == 2
                       else bias[:, None, None, :, :])
    m = jnp.max(logits, axis=-1)                      # (B,Hkv,G,Sq)
    p = jnp.exp(logits - m[..., None])
    s = jnp.sum(p, axis=-1)                           # (B,Hkv,G,Sq)
    # probabilities in the value dtype for the AV matmul: halves the
    # dominant HBM tile traffic of long-sequence prefill (p in [0,1] is
    # safe in bf16; the normalizer s stays f32). See EXPERIMENTS §Perf.
    o = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, s


def full_attention(q, k, v, cfg: ModelConfig, is_global,
                   q_positions: jax.Array, k_positions: jax.Array,
                   kv_len: Optional[jax.Array] = None,
                   kv_chunk: int = 1024) -> jax.Array:
    """Flash-style attention scanning over KV chunks (online softmax).

    Shapes: q (B,Sq,Hq,hd), k/v (B,Sk,Hkv,hd). Returns (B,Sq,Hq,hd).
    Memory: O(Sq * kv_chunk) score tiles instead of O(Sq * Sk).
    ``q_positions`` may be (Sq,) or per-row (B,Sq), and ``kv_len`` a
    scalar or per-row (B,) — see ``_mask_bias``.
    """
    B, Sq, Hq, _ = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    hd = v.shape[-1]  # the output's head width: the values'
    if Sk <= kv_chunk:
        bias = _mask_bias(q_positions, k_positions, cfg, is_global, kv_len)
        o, m, s = _sdpa_chunk(q, k, v, bias, cfg)
        out = o / jnp.maximum(s[..., None], 1e-30)
        return out.reshape(B, Hkv, G, Sq, hd).transpose(0, 3, 1, 2, 4) \
                  .reshape(B, Sq, Hq, hd).astype(q.dtype)

    n_chunks = Sk // kv_chunk
    assert Sk % kv_chunk == 0, "kv length must be divisible by kv_chunk"
    ks = k.reshape(B, n_chunks, kv_chunk, Hkv, k.shape[-1])
    vs = v.reshape(B, n_chunks, kv_chunk, Hkv, hd)
    kpos = k_positions.reshape(n_chunks, kv_chunk)

    def step(carry, xs):
        o_acc, m_acc, s_acc = carry
        kc, vc, kp = xs
        bias = _mask_bias(q_positions, kp, cfg, is_global, kv_len)
        o, m, s = _sdpa_chunk(q, kc, vc, bias, cfg)
        m_new = jnp.maximum(m_acc, m)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m - m_new)
        o_acc = o_acc * alpha[..., None] + o * beta[..., None]
        s_acc = s_acc * alpha + s * beta
        return (o_acc, m_acc * 0 + m_new, s_acc), None

    o0 = jnp.zeros((B, Hkv, G, Sq, hd), jnp.float32)
    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    s0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    (o, _, s), _ = jax.lax.scan(
        step, (o0, m0, s0),
        (ks.transpose(1, 0, 2, 3, 4), vs.transpose(1, 0, 2, 3, 4), kpos))
    out = o / jnp.maximum(s[..., None], 1e-30)
    return out.reshape(B, Hkv, G, Sq, hd).transpose(0, 3, 1, 2, 4) \
              .reshape(B, Sq, Hq, hd).astype(q.dtype)


def _repeat_kv_factor(cfg: ModelConfig, plan) -> int:
    """KV replication factor when q heads shard over TP but kv heads
    don't divide the axis (vLLM-style): repeat kv up to the q head count
    (G=1) so the GQA grouping reshape never splits a sharded head dim.
    The single source of truth for prefill (``_maybe_repeat_kv``) and
    decode (the ``repeat_kv`` dispatch argument) alike."""
    if plan is None or plan.is_null or plan.attn_mode != "tp_heads":
        return 1
    tp = plan.axis_size(plan.attn_tp_axis)
    if cfg.num_kv_heads % tp == 0 or cfg.num_heads % tp != 0:
        return 1
    return cfg.num_heads // cfg.num_kv_heads


def _maybe_repeat_kv(k, v, cfg: ModelConfig, plan):
    """Apply ``_repeat_kv_factor`` to a (B, S, Hkv, hd) pair."""
    g = _repeat_kv_factor(cfg, plan)
    if g == 1:
        return k, v, False
    return jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2), True


def attention_block(x: jax.Array, w: AttnTemps, cfg: ModelConfig,
                    is_global, plan, q_chunk: int = 512,
                    return_kv: bool = False, backend=None):
    """Full-sequence attention (training / prefill): (B,S,d) -> (B,S,d).

    ``return_kv=True`` also returns the (pre-replication, rope'd) K/V so
    prefill can seed the decode cache without re-projecting them.

    ``backend`` selects the kernel path for the attention proper
    (DESIGN.md §4c): ``pallas`` routes causal prefill through
    ``ops.flash_attention`` — shard_map'ed over the plan's TP axis when
    the (post-replication) head counts divide it — while ``ref``/None
    keeps the chunked jnp flash below, whose numerics the greedy
    equivalence tests pin. Replicated-attention and non-dividing plans
    always keep the jnp path.
    """
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    q, k, v = qkv_project(x, w, cfg, positions[None, :])
    kv_out = (k, v) if return_kv else None
    k, v, repeated = _maybe_repeat_kv(k, v, cfg, plan)
    use_kernel = (kernel_ops.resolve_backend(backend)
                  is kernel_ops.KernelBackend.PALLAS and cfg.causal)
    shard_axes = None
    if plan is not None and not plan.is_null:
        heads_sharded = plan.attn_mode == "tp_heads"
        q = plan.constrain(q, plan.act_bthd(heads_sharded))
        kv_ok = heads_sharded and (repeated or cfg.num_kv_heads % plan.axis_size(
            plan.attn_tp_axis) == 0)
        k = plan.constrain(k, plan.act_bthd(kv_ok))
        v = plan.constrain(v, plan.act_bthd(kv_ok))
        # the kernel runs per head shard: only a heads-on-TP plan whose
        # (post-replication) head counts divide the axis maps onto it
        shard_axes = plan.attn_kernel_axes(cfg.num_heads, k.shape[2])
        use_kernel = use_kernel and shard_axes is not None

    if not use_kernel:
        kernel_ops.DISPATCH_COUNTS["flash.ref"] += 1
    if use_kernel:
        out = kernel_ops.flash_attention(
            q, k, v, is_global=is_global, window=cfg.sliding_window,
            softcap=cfg.attn_logit_softcap, scale=_scale(cfg),
            shard_axes=shard_axes, backend=kernel_ops.KernelBackend.PALLAS)
    elif S > q_chunk and S % q_chunk == 0:
        nq = S // q_chunk
        qs = q.reshape(B, nq, q_chunk, cfg.num_heads, cfg.head_dim)

        def one_q_chunk(i):
            qp = jax.lax.dynamic_slice(positions, (i * q_chunk,), (q_chunk,))
            return full_attention(qs[:, i], k, v, cfg, is_global,
                                  qp, positions)
        out = jax.lax.map(one_q_chunk, jnp.arange(nq))      # (nq,B,qc,H,hd)
        out = out.transpose(1, 0, 2, 3, 4).reshape(B, S, cfg.num_heads,
                                                   cfg.head_dim)
    else:
        out = full_attention(q, k, v, cfg, is_global, positions, positions)
    o = jnp.einsum("bse,ed->bsd", out.reshape(B, S, -1).astype(x.dtype),
                   w.wo, preferred_element_type=x.dtype)
    if return_kv:
        return o, kv_out
    return o


def prefill_kv(x: jax.Array, w: AttnTemps, cfg: ModelConfig):
    """Compute the K/V tensors to seed a decode cache: (B,S,Hkv,hd) pair."""
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    _, k, v = qkv_project(x, w, cfg, positions)
    return k, v


def decode_attention(x: jax.Array, w: AttnTemps, cfg: ModelConfig,
                     is_global, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, plan,
                     block_tables: Optional[jax.Array] = None,
                     prefix_groups: Optional[jax.Array] = None,
                     backend=None) -> tuple:
    """Cache-appending attention: one decode token or one prefill chunk.

    x: (B, C, d) — C == 1 is plain decode; C > 1 is a chunked-prefill
    append (paged caches only): the chunk's K/V are written at positions
    ``pos[i] .. pos[i]+C-1`` and each query attends causally over the
    cache prefix plus the chunk's own earlier tokens.

    ``pos`` is a scalar (lockstep batch: every row decodes at the same
    depth) or a (B,) vector (continuous batching: each row sits at its
    own depth — RoPE angles, cache writes and validity masks are all
    per-row; see DESIGN.md §4b).

    Caches are contiguous ``(B, Smax, Hkv, hd)`` when ``block_tables`` is
    None, else paged ``(num_blocks, block_size, Hkv, hd)`` pages shared
    by all rows, with ``block_tables`` (B, max_blocks) mapping each row's
    logical positions to physical blocks (trash-block semantics and the
    causality-only validity argument live with the kernels —
    ``repro.kernels.ref.paged_attention_ref`` /
    ``repro.kernels.paged_attention``). ``prefix_groups`` (2, B) routes
    shared prefix blocks through their group representative's table —
    the prefix-cache kernel path (DESIGN.md §4d), paged only.

    This function is projection + dispatch: the scatter/gather/attend
    step itself runs in ``repro.kernels.ops.decode_attention`` under the
    selected ``backend`` ("ref" | "pallas" | None for auto). Returns
    (out (B,C,d), new_k_cache, new_v_cache).
    """
    B, C = x.shape[0], x.shape[1]
    pos = jnp.asarray(pos, jnp.int32)  # callers mix python ints and arrays
    q_pos = ((pos[:, None] if pos.ndim else pos[None, None])
             + jnp.arange(C, dtype=jnp.int32))          # (B|1, C)
    q, k_new, v_new = qkv_project(x, w, cfg, q_pos)

    constrain = None
    shard_axes = None
    if plan is not None and not plan.is_null:
        if block_tables is None or plan.kv_shard == "heads":
            def constrain(c, _plan=plan):
                return _plan.constrain(c, _plan.cache_spec_bshd())
        # heads-sharded plans with dividing head counts run the Pallas
        # kernel per KV shard under shard_map; others (repeat_kv, seq-
        # sharded caches) keep ref under the same seam (DESIGN.md §4c)
        shard_axes = plan.decode_kernel_axes(cfg.num_heads, cfg.num_kv_heads)
    repeat = _repeat_kv_factor(cfg, plan) if block_tables is not None else 1

    out, k_cache, v_cache = kernel_ops.decode_attention(
        q, k_cache, v_cache, k_new, v_new, pos,
        block_tables=block_tables, prefix_groups=prefix_groups,
        scale=_scale(cfg),
        softcap=cfg.attn_logit_softcap, window=cfg.sliding_window,
        is_global=is_global, trash_block=TRASH_BLOCK, repeat_kv=repeat,
        constrain=constrain, shard_axes=shard_axes,
        sharded=plan is not None and not plan.is_null, backend=backend)
    o = jnp.einsum("bse,ed->bsd", out.reshape(B, C, -1).astype(x.dtype),
                   w.wo, preferred_element_type=x.dtype)
    return o, k_cache, v_cache


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2, arXiv:2405.04434)
# ---------------------------------------------------------------------------
class MLATemps(NamedTuple):
    """Per-layer MLA weights, already unstacked (no leading L).

    wq (d, H*(dn+dr)): per head its dn columns without positions, then
    its dr roped ones; wkv_a (d, r+dr): the latent c, then the rope key
    shared by all heads; kv_norm (r,): c's RMS-norm scale; wkv_b
    (r, H*(dn+dv)): per head the key up-projection W_UK (dn columns),
    then the value up-projection W_UV (dv); wo (H*dv, d)."""
    wq: jax.Array
    wkv_a: jax.Array
    kv_norm: jax.Array
    wkv_b: jax.Array
    wo: jax.Array


def mla_project(x: jax.Array, w: MLATemps, cfg: ModelConfig,
                positions: jax.Array):
    """x (B, S, d) -> q_nope (B,S,H,dn), q_pe (B,S,H,dr) roped, and the
    latent cache rows (B, S, latent_width): c RMS-normed, k_pe roped,
    zeros to the row's width.

    Rope rotates the two halves of each rope block. DeepSeek's own code
    de-interleaves the (even, odd) columns of q_pe and k_pe before it
    rotates halves; that is a fixed permutation of wq's and wkv_a's rope
    columns, which random weights do not see, and the references apply
    the same convention (``repro.models.reference``)."""
    B, S, _ = x.shape
    H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r = cfg.kv_lora_rank
    yarn = _yarn(cfg)
    q = jnp.einsum("bsd,de->bse", x, w.wq).reshape(B, S, H, dn + dr)
    q_nope = q[..., :dn]
    q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta, yarn)
    kv = jnp.einsum("bsd,de->bse", x, w.wkv_a)                # (B, S, r+dr)
    c = rms_norm(kv[..., :r], w.kv_norm, cfg.norm_eps)
    k_pe = apply_rope(kv[..., None, r:], positions, cfg.rope_theta, yarn)[..., 0, :]
    pad = jnp.zeros((B, S, cfg.latent_width - r - dr), x.dtype)
    return q_nope, q_pe, jnp.concatenate([c, k_pe, pad], axis=-1)


def _kv_chunk(n: int) -> int:
    """The key block of the decompressed attention: 512 positions where
    they divide the context, else all of it in one."""
    return 512 if n % 512 == 0 else n


def mla_attend(q_nope, q_pe, rows, w: MLATemps, cfg: ModelConfig,
               q_positions, k_positions) -> jax.Array:
    """Attention in the decompressed head space: every latent row of the
    context through W_kvb into each head's 128-wide key and value, keys
    completed by the shared rope key, then causal flash attention.
    rows (B, T, latent_width) -> (B, Sq, H*dv)."""
    B, T, _ = rows.shape
    H, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim)
    r = cfg.kv_lora_rank
    with jax.named_scope("kv_b"):
        kv = jnp.einsum("btr,re->bte", rows[..., :r], w.wkv_b).reshape(
            B, T, H, dn + dv)
    k_pe = jnp.broadcast_to(rows[:, :, None, r:r + dr], (B, T, H, dr))
    k = jnp.concatenate([kv[..., :dn], k_pe], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    with jax.named_scope("mla_attend"):
        out = full_attention(q, k, kv[..., dn:], cfg, True, q_positions,
                             k_positions, kv_chunk=_kv_chunk(T))
    return out.reshape(B, q.shape[1], H * dv)


def mla_attention_block(x: jax.Array, w: MLATemps, cfg: ModelConfig) -> jax.Array:
    """Full-sequence MLA (training / forward): (B,S,d) -> (B,S,d)."""
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    q_nope, q_pe, rows = mla_project(x, w, cfg, positions[None, :])
    out = mla_attend(q_nope, q_pe, rows, w, cfg, positions, positions)
    return jnp.einsum("bse,ed->bsd", out.astype(x.dtype), w.wo,
                      preferred_element_type=x.dtype)


def write_latent_rows(pool: jax.Array, layer, rows: jax.Array,
                      positions: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Scatter rows (B, C, W) into ``pool[layer]`` (pool (L, N, bs, W)) at
    each row's ``positions`` (B, C) through its block table; positions past
    the table width land in the trash block, never in a live page."""
    bs = pool.shape[2]
    max_blocks = block_tables.shape[1]
    blk, off = positions // bs, positions % bs
    phys = jnp.take_along_axis(block_tables, jnp.clip(blk, 0, max_blocks - 1), axis=1)
    phys = jnp.where(blk < max_blocks, phys, TRASH_BLOCK)
    return pool.at[layer, phys, off].set(rows.astype(pool.dtype))


def mla_cached_attention(x: jax.Array, w: MLATemps, cfg: ModelConfig,
                         pool: jax.Array, layer, pos: jax.Array,
                         block_tables: jax.Array, backend=None):
    """Cache-appending MLA over the paged latent pool: one decode token or
    one prefill chunk a row.

    x (B, C, d); pool (L, N, bs, latent_width): every layer's latent pages,
    ``layer`` the one this call reads and writes; pos (B,) (or a scalar)
    the rows' first new position; block_tables (B, max_blocks). The C new
    latent rows are written first, so each query attends its own token.

    C == 1 (decode) attends in the latent space: W_UK folds into each
    head's query (``mla_absorb``), the kernel seam
    (``ops.mla_decode_attention``: Pallas or its jnp oracle) scores the
    queries against the whole latent rows and sums their first r columns
    (``mla_attend``), and W_UV follows. C > 1 (a prefill chunk)
    decompresses the row's whole table through W_kvb and attends in the
    head space (``mla_attend``, XLA): for 512 queries that is about half
    the operations of the latent-space product. Returns (out (B, C, d),
    pool)."""
    B, C = x.shape[0], x.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q_pos = pos[:, None] + jnp.arange(C, dtype=jnp.int32)       # (B, C)
    q_nope, q_pe, rows = mla_project(x, w, cfg, q_pos)
    with jax.named_scope("kv_write"):
        pool = write_latent_rows(pool, layer, rows, q_pos, block_tables)
    H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    if C == 1:
        wkv_b = w.wkv_b.reshape(r, H, dn + dv)
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], wkv_b[..., :dn],
                               preferred_element_type=jnp.float32)
            pad = jnp.zeros((B, H, cfg.latent_width - r - q_pe.shape[-1]), pool.dtype)
            q_abs = jnp.concatenate(
                [q_lat.astype(pool.dtype), q_pe[:, 0].astype(pool.dtype), pad], -1)
        with jax.named_scope("mla_attend"):
            o_lat = kernel_ops.mla_decode_attention(
                q_abs, pool, block_tables, pos, layer, kv_rank=r, scale=_scale(cfg),
                backend=backend)
        with jax.named_scope("mla_absorb"):
            out = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype), wkv_b[..., dn:],
                             preferred_element_type=x.dtype).reshape(B, 1, H * dv)
    else:
        kernel_ops.DISPATCH_COUNTS["mla_chunk.xla"] += 1
        bs = pool.shape[2]
        T = block_tables.shape[1] * bs
        ctx = pool[layer, block_tables]  # one gather: no copy of the layer
        out = mla_attend(q_nope, q_pe, ctx.reshape(B, T, -1), w, cfg, q_pos,
                         jnp.arange(T, dtype=jnp.int32))
    o = jnp.einsum("bse,ed->bsd", out.astype(x.dtype), w.wo,
                   preferred_element_type=x.dtype)
    return o, pool
