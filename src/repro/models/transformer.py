"""The generic stacked-layer transformer covering the whole model zoo.

One layer body, scanned with ``jax.lax.scan`` over stacked parameters
(leading L axis). Variants (dense / MoE / mamba / hybrid / encoder-only /
VLM-audio frontends) are selected by ``ModelConfig`` flags; per-layer
local-vs-global attention comes in as a traced bool array so weight shapes
stay uniform.

Leading dense layers (``cfg.first_k_dense_replace``, DeepSeek's dense
first layer) run before the scan, one at a time, each from its own
unstacked weights (``params["dense_layers"]["0"]``, ...); the scan covers
the MoE layers that follow.
Multi-head latent attention (``cfg.is_mla``) keeps one latent row a token
in a paged pool that every layer's pages share (``init_paged_cache``),
carried through the layer loop and written in place.

Public entry points:
  loss_and_aux   — training loss (LM CE + MoE aux)
  prefill        — full forward returning last-position logits + decode cache
  decode_step    — one-token step updating the cache
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from . import attention as attn_mod
from . import mamba as mamba_mod
from . import moe as moe_mod
from .common import cross_entropy_loss, glu_ffn, plain_ffn, rms_norm, softcap


# Scan-unroll control for the dry-run's per-layer cost probes (an unrolled
# 2-layer vs 1-layer compile isolates one layer's FLOPs/bytes/collectives,
# since XLA's cost_analysis counts a while-loop body only once).
_SCAN_UNROLL: list = [1]


@contextlib.contextmanager
def scan_unroll(n):
    _SCAN_UNROLL.append(n)
    try:
        yield
    finally:
        _SCAN_UNROLL.pop()


def _scan(*args, **kw):
    return jax.lax.scan(*args, unroll=_SCAN_UNROLL[-1], **kw)


class DecodeCache(NamedTuple):
    """Decode-time state. Unused fields are None for a given family."""
    k: Optional[jax.Array]     # (L, B, Smax, Hkv, hd) contiguous, or
    #                            (L, num_blocks, block_size, Hkv, hd) paged
    v: Optional[jax.Array]
    conv: Optional[jax.Array]  # (L, B, cw-1, d_inner)
    ssm: Optional[jax.Array]   # (L, B, d_inner, N) float32
    pos: jax.Array             # int32 tokens written so far: scalar for a
    #                            lockstep batch, (B,) per-row under
    #                            continuous batching (DESIGN.md §4b)
    block_tables: Optional[jax.Array] = None  # (B, max_blocks) int32 for a
    #                            paged cache (None => contiguous layout);
    #                            unused entries point at trash block 0
    prefix_groups: Optional[jax.Array] = None  # (2, B) int32 prefix-cache
    #                            grouping (paged only, DESIGN.md §4d):
    #                            row 0 = each row's group representative,
    #                            row 1 = shared leading block count; None
    #                            disables the prefix-aware kernel path
    route_topk: Optional[jax.Array] = None  # (L, B*C, top_k) int32 router
    #                            top-k ids of the step just taken, present
    #                            only when decode_step ran with
    #                            collect_routing=True — the engine's
    #                            hot-expert replication tracker reads it
    #                            and strips it before the next step


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------
def embed_tokens(params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def embed_inputs(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                 plan) -> jax.Array:
    """Build the (B, S, d) input sequence for any modality."""
    if cfg.frontend == "audio":
        x = jnp.einsum("bsf,fd->bsd",
                       batch["features"].astype(params["embed"].dtype),
                       params["frontend_proj"])
    elif cfg.frontend == "vision":
        patches = jnp.einsum("bpf,fd->bpd",
                             batch["patches"].astype(params["embed"].dtype),
                             params["frontend_proj"])
        toks = embed_tokens(params, cfg, batch["tokens"])
        x = jnp.concatenate([patches, toks], axis=1)
    else:
        x = embed_tokens(params, cfg, batch["tokens"])
    if plan is not None and not plan.is_null:
        x = plan.constrain(x, plan.act_btd())
    return x


@jax.named_scope("head")
def unembed(params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    h = rms_norm(hidden, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"])
    if cfg.final_logit_softcap > 0:
        logits = softcap(logits, cfg.final_logit_softcap)
    return logits


# ---------------------------------------------------------------------------
# one layer — full-sequence (train / prefill)
# ---------------------------------------------------------------------------
def _sp_gather(h, plan):
    """Megatron-SP: residuals live sequence-sharded between layers; gather
    the full sequence (all-gather on the TP axis) right before the big
    projections, so K/V never need an implicit seq->head reshard."""
    if plan is not None and not plan.is_null and plan.seq_shard_acts:
        return plan.constrain(h, P(plan.dp, None, None))
    return h


def _mixer_full(x, lp, flag, cfg: ModelConfig, plan, collect_kv: bool,
                backend=None):
    """Attention / mamba / hybrid sublayer. Returns (mixed, (k, v) or None)."""
    kv = None
    h = _sp_gather(rms_norm(x, lp["ln1"], cfg.norm_eps), plan)
    if cfg.is_mla:
        if collect_kv:
            raise NotImplementedError(_MLA_PAGED_ONLY)
        out = attn_mod.mla_attention_block(h, attn_mod.MLATemps(**lp["attn"]), cfg)
    elif cfg.block_type == "attention":
        w = attn_mod.AttnTemps(**lp["attn"])
        if collect_kv:
            out, kv = attn_mod.attention_block(h, w, cfg, flag, plan,
                                               return_kv=True,
                                               backend=backend)
        else:
            out = attn_mod.attention_block(h, w, cfg, flag, plan,
                                           backend=backend)
    elif cfg.block_type == "mamba":
        out = mamba_mod.mamba_mixer(h, lp["mamba"], cfg, plan)
    else:  # hybrid — parallel attention + mamba heads, normed fusion
        w = attn_mod.AttnTemps(**lp["attn"])
        if collect_kv:
            a_out, kv = attn_mod.attention_block(h, w, cfg, flag, plan,
                                                 return_kv=True,
                                                 backend=backend)
        else:
            a_out = attn_mod.attention_block(h, w, cfg, flag, plan,
                                             backend=backend)
        m_out = mamba_mod.mamba_mixer(h, lp["mamba"], cfg, plan)
        out = 0.5 * (rms_norm(a_out, lp["fuse_norm_attn"], cfg.norm_eps)
                     + rms_norm(m_out, lp["fuse_norm_mamba"], cfg.norm_eps))
    if cfg.use_post_norm:
        out = rms_norm(out, lp["ln1_post"], cfg.norm_eps)
    return out, kv


def _ffn_full(x, lp, cfg: ModelConfig, plan, backend=None):
    """FFN / MoE sublayer. Returns (out, aux_loss, route_idx).

    ``route_idx`` is the router's top-k ids ((B*S, k) int32, MoE only,
    None otherwise) — the decode body threads it out through the layer
    scan for the engine's routing-frequency tracker."""
    if cfg.ffn_type == "none":
        return jnp.zeros_like(x), jnp.zeros((), jnp.float32), None
    h = _sp_gather(rms_norm(x, lp["ln2"], cfg.norm_eps), plan)
    route_idx = None
    if cfg.ffn_type == "dense":
        if cfg.activation in ("silu", "gelu"):
            out = glu_ffn(h, lp["ffn"]["wi_gate"], lp["ffn"]["wi_up"],
                          lp["ffn"]["wo"], cfg.activation)
        else:
            out = plain_ffn(h, lp["ffn"]["wi"], lp["ffn"]["wo"],
                            cfg.activation)
        aux = jnp.zeros((), jnp.float32)
    else:
        res = moe_mod.apply_moe(h, lp["moe"], cfg, plan, backend=backend)
        out, aux, route_idx = res.y, res.aux_loss, res.route_idx
    if cfg.use_post_norm:
        out = rms_norm(out, lp["ln2_post"], cfg.norm_eps)
    return out, aux, route_idx


def _dense_ffn(x, lp, cfg: ModelConfig, plan):
    """A leading dense layer's FFN sublayer (width ``cfg.d_ff``)."""
    with jax.named_scope("dense_ffn"):
        h = _sp_gather(rms_norm(x, lp["ln2"], cfg.norm_eps), plan)
        f = lp["ffn"]
        return glu_ffn(h, f["wi_gate"], f["wi_up"], f["wo"], cfg.activation)


def _dense_layer(params, i: int):
    """The weights of leading dense layer ``i``."""
    return params["dense_layers"][str(i)]


def dense_layers_full(x, params, cfg: ModelConfig, plan, collect_kv: bool = False,
                      backend=None):
    """The leading dense layers over a full sequence, one at a time.
    Returns (x, [(k, v) per layer] when ``collect_kv``)."""
    flags = cfg.global_layer_flags()
    kvs = []
    for i in range(cfg.num_dense_layers):
        lp = _dense_layer(params, i)
        with jax.named_scope("attn"):
            mixed, kv = _mixer_full(x, lp, jnp.asarray(flags[i]), cfg, plan,
                                    collect_kv, backend)
        x = x + mixed
        x = x + _dense_ffn(x, lp, cfg, plan)
        if plan is not None and not plan.is_null:
            x = plan.constrain(x, plan.act_btd())
        kvs.append(kv)
    return x, kvs


def layer_full(x, lp, flag, cfg: ModelConfig, plan, collect_kv: bool = False,
               backend=None):
    with jax.named_scope("attn"):
        mixed, kv = _mixer_full(x, lp, flag, cfg, plan, collect_kv, backend)
    x = x + mixed
    if plan is not None and not plan.is_null:
        x = plan.constrain(x, plan.act_btd())
    ffn_out, aux, _ = _ffn_full(x, lp, cfg, plan, backend)
    x = x + ffn_out
    if plan is not None and not plan.is_null:
        x = plan.constrain(x, plan.act_btd())
    return x, kv, aux


# ---------------------------------------------------------------------------
# full forward (train / prefill)
# ---------------------------------------------------------------------------
def _layer_flags(cfg: ModelConfig) -> jax.Array:
    """Local/global flags of the scanned layers (those after the leading
    dense ones)."""
    flags = cfg.global_layer_flags()[cfg.num_dense_layers:]
    return jnp.asarray(flags, dtype=bool)


_MLA_PAGED_ONLY = ("multi-head latent attention keeps its latent cache in the "
                   "paged pool: serve it through the continuous engine (paged "
                   "KV, chunked prefill)")


def forward_hidden(params, cfg: ModelConfig, x: jax.Array, plan,
                   collect_kv: bool = False, remat: bool = False,
                   backend="ref"):
    """Scan the layer stack. Returns (hidden, (k_all, v_all) or None, aux).

    ``backend`` pins the kernel seam to the jnp reference by default:
    this entry is differentiated by training, and the Pallas kernels
    define no VJP — the inference stack (``prefill``/``decode_step``)
    threads the engine's backend instead.
    """
    flags = _layer_flags(cfg)
    x, dense_kv = dense_layers_full(x, params, cfg, plan, collect_kv, backend)

    def body(carry, per_layer):
        h, aux_acc = carry
        lp, flag = per_layer
        h, kv, aux = layer_full(h, lp, flag, cfg, plan, collect_kv, backend)
        return (h, aux_acc + aux), kv

    if remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        body_fn = jax.checkpoint(body, policy=policy)
    elif remat:
        body_fn = jax.checkpoint(body)
    else:
        body_fn = body
    (h, aux), kvs = _scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                          (params["layers"], flags))
    if collect_kv and dense_kv:
        kvs = _stack_front(dense_kv, kvs)
    return h, kvs, aux


def _stack_front(front, stacked):
    """Per-layer (k, v) pairs of the leading layers stacked in front of the
    scanned layers' stacked pair."""
    return tuple(jnp.concatenate([jnp.stack([kv[i] for kv in front]), stacked[i]])
                 for i in range(2))


def loss_and_aux(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                 plan=None, remat: bool = True):
    """Training objective: next-token CE (+ MoE load-balance aux).

    - decoder LMs: predict batch["labels"] (B, S)
    - encoder-only (hubert): masked-prediction CE over all frames
    - VLM: labels cover only the text positions (patches are context)
    """
    x = embed_inputs(params, cfg, batch, plan)
    h, _, aux = forward_hidden(params, cfg, x, plan, remat=remat)
    if cfg.frontend == "vision":
        n_text = batch["tokens"].shape[1]
        h = h[:, -n_text:, :]
    logits = unembed(params, cfg, h)
    loss = cross_entropy_loss(logits, batch["labels"],
                              batch.get("loss_mask"))
    return loss + aux, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, plan=None) -> DecodeCache:
    if cfg.is_mla:
        raise NotImplementedError(_MLA_PAGED_ONLY)
    L = cfg.num_layers
    k = v = conv = ssm = None
    if cfg.has_attention:
        kv_dt = jnp.dtype(cfg.kv_cache_dtype) if cfg.kv_cache_dtype \
            else dtype
        shape = (L, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        k = jnp.zeros(shape, kv_dt)
        v = jnp.zeros(shape, kv_dt)
        if plan is not None and not plan.is_null:
            k = plan.constrain(k, plan.kv_cache_spec())
            v = plan.constrain(v, plan.kv_cache_spec())
    if cfg.has_mamba:
        conv = jnp.zeros((L, batch, cfg.ssm_conv - 1, cfg.ssm_d_inner), dtype)
        ssm = jnp.zeros((L, batch, cfg.ssm_d_inner, cfg.ssm_state),
                        jnp.float32)
        if plan is not None and not plan.is_null:
            conv = plan.constrain(conv, plan.conv_cache_spec())
            ssm = plan.constrain(ssm, plan.ssm_cache_spec())
    return DecodeCache(k=k, v=v, conv=conv, ssm=ssm,
                       pos=jnp.zeros((), jnp.int32))


def init_paged_cache(cfg: ModelConfig, nslots: int, num_blocks: int,
                     block_size: int, max_blocks: int,
                     dtype=jnp.bfloat16, plan=None) -> DecodeCache:
    """A block-pooled decode cache (DESIGN.md §4b): K/V pages shared by
    all ``nslots`` live rows, addressed through per-row block tables.

    ``num_blocks`` includes the reserved trash block 0 (see
    ``repro.serving.kv_cache``). Mamba state is not paged — attention-only
    models for now; the serving engine falls back to contiguous slots for
    mamba/hybrid families.

    Multi-head latent attention pages one latent row a token and layer,
    key and value at once: ``k`` is ``(L, num_blocks, block_size,
    cfg.latent_width)`` and ``v`` is None.
    """
    assert cfg.has_attention and not cfg.has_mamba, \
        "paged caches cover attention KV only (mamba state is unpaged)"
    L = cfg.num_layers
    kv_dt = jnp.dtype(cfg.kv_cache_dtype) if cfg.kv_cache_dtype else dtype
    tables = jnp.zeros((nslots, max_blocks), jnp.int32)
    if cfg.is_mla:
        return DecodeCache(
            k=jnp.zeros((L, num_blocks, block_size, cfg.latent_width), kv_dt),
            v=None, conv=None, ssm=None,
            pos=jnp.zeros((nslots,), jnp.int32), block_tables=tables)
    shape = (L, num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
    k = jnp.zeros(shape, kv_dt)
    v = jnp.zeros(shape, kv_dt)
    if plan is not None and not plan.is_null and plan.kv_shard == "heads":
        k = plan.constrain(k, plan.kv_cache_spec())
        v = plan.constrain(v, plan.kv_cache_spec())
    return DecodeCache(
        k=k, v=v, conv=None, ssm=None,
        pos=jnp.zeros((nslots,), jnp.int32), block_tables=tables)


def prefill(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            max_len: int, plan=None, backend=None
            ) -> Tuple[jax.Array, DecodeCache]:
    """Process the prompt; return (last-position logits, primed cache).

    The KV cache is allocated at ``max_len`` and the prompt's K/V written at
    the front. Mamba state caches are produced by re-running the recurrence
    carry (collected from the chunked scan).

    ``backend`` selects the kernel path for prefill attention and the
    expert FFNs ("ref" | "pallas" | None for auto) — the engine threads
    its ``kernel_backend`` here so prefill rides the same seam as decode
    (DESIGN.md §Kernel backends).
    """
    assert cfg.causal, "prefill/decode only for decoder models"
    if cfg.is_mla:
        raise NotImplementedError(_MLA_PAGED_ONLY)
    x = embed_inputs(params, cfg, batch, plan)
    B, S = x.shape[0], x.shape[1]

    flags = _layer_flags(cfg)
    x, dense_kv = dense_layers_full(x, params, cfg, plan, cfg.has_attention,
                                    backend)
    body = make_prefill_body(cfg, plan, backend)
    (h, _aux), ys = _scan(
        body, (x, jnp.zeros((), jnp.float32)), (params["layers"], flags))
    if dense_kv:
        ys["kv"] = _stack_front(dense_kv, ys["kv"])
    return _prefill_finish(params, cfg, h, ys, B, S, max_len, plan)


def make_prefill_body(cfg: ModelConfig, plan, backend=None):
    """The prefill layer-scan body (exposed for the dry-run cost probe)."""
    collect_kv = cfg.has_attention

    def body(carry, per_layer):
        h, aux_acc = carry
        lp, flag = per_layer
        ys: Dict[str, Any] = {}
        if cfg.has_mamba:
            # run the mixer pieces separately to also extract final state
            hn = _sp_gather(rms_norm(h, lp["ln1"], cfg.norm_eps), plan)
            m_out, m_state = _mamba_with_state(hn, lp["mamba"], cfg)
            if cfg.block_type == "hybrid":
                w = attn_mod.AttnTemps(**lp["attn"])
                a_out, kv = attn_mod.attention_block(hn, w, cfg, flag,
                                                     plan, return_kv=True,
                                                     backend=backend)
                out = 0.5 * (rms_norm(a_out, lp["fuse_norm_attn"],
                                      cfg.norm_eps)
                             + rms_norm(m_out, lp["fuse_norm_mamba"],
                                        cfg.norm_eps))
                ys["kv"] = kv
            else:
                out = m_out
            if cfg.use_post_norm:
                out = rms_norm(out, lp["ln1_post"], cfg.norm_eps)
            h = h + out
            ys["conv"] = m_state[0]
            ys["ssm"] = m_state[1]
            ffn_out, aux, _ = _ffn_full(h, lp, cfg, plan, backend)
            h = h + ffn_out
        else:
            h, kv, aux = layer_full(h, lp, flag, cfg, plan,
                                    collect_kv=collect_kv, backend=backend)
            ys["kv"] = kv
        return (h, aux_acc + aux), ys

    return body


def _prefill_finish(params, cfg: ModelConfig, h, ys, B, S, max_len, plan):
    cache = init_cache(cfg, B, max_len, dtype=h.dtype, plan=plan)
    if cfg.has_attention:
        with jax.named_scope("kv_write"):
            k_new = ys["kv"][0].astype(cache.k.dtype)   # (L, B, S, Hkv, hd)
            v_new = ys["kv"][1].astype(cache.v.dtype)
            k = jax.lax.dynamic_update_slice(cache.k, k_new, (0, 0, 0, 0, 0))
            v = jax.lax.dynamic_update_slice(cache.v, v_new, (0, 0, 0, 0, 0))
            if plan is not None and not plan.is_null:
                k = plan.constrain(k, plan.kv_cache_spec())
                v = plan.constrain(v, plan.kv_cache_spec())
        cache = cache._replace(k=k, v=v)
    if cfg.has_mamba:
        cache = cache._replace(conv=ys["conv"].astype(cache.conv.dtype),
                               ssm=ys["ssm"])
    cache = cache._replace(pos=jnp.asarray(S, jnp.int32))

    logits = unembed(params, cfg, h[:, -1:, :])
    return logits[:, 0], cache


def _mamba_with_state(h, mp, cfg: ModelConfig):
    """mamba_mixer + final (conv_window, ssm_state) for cache priming."""
    out = mamba_mod.mamba_mixer(h, mp, cfg)
    # trailing conv inputs: recompute in_proj tail (cheap: last cw-1 tokens)
    cw = cfg.ssm_conv
    tail = h[:, -(cw - 1):, :]
    xz = jnp.einsum("bsd,de->bse", tail, mp["in_proj"])
    x_tail = jnp.split(xz, 2, axis=-1)[0]
    # final ssm state: rerun the recurrence on the full sequence but only
    # keep the carry — reuse the chunked scan's final state by calling the
    # mixer's internal pieces.
    state = _mamba_final_state(h, mp, cfg)
    return out, (x_tail, state)


def _mamba_final_state(h, mp, cfg: ModelConfig, chunk: int = 256):
    B, S, _ = h.shape
    xz = jnp.einsum("bsd,de->bse", h, mp["in_proj"])
    x_in, _ = jnp.split(xz, 2, axis=-1)
    x_c = jax.nn.silu(mamba_mod._causal_conv(x_in, mp["conv_w"],
                                             mp["conv_b"]))
    dt, B_ssm, _, A = mamba_mod._ssm_inputs(x_c, mp, cfg)
    xf = x_c.astype(jnp.float32)
    cs = min(chunk, S)
    while S % cs:
        cs -= 1
    n_chunks = S // cs

    def split(t):
        return t.reshape((B, n_chunks, cs) + t.shape[2:]).swapaxes(0, 1)

    def step(hc, xs):
        dt_c, b_c, x_cc = xs
        a_bar = jnp.exp(dt_c[..., None] * A)
        bx = (dt_c * x_cc)[..., None] * b_c[:, :, None, :]
        _, h_last = mamba_mod._scan_chunk(a_bar, bx, hc)
        return h_last, None

    h0 = jnp.zeros((B, cfg.ssm_d_inner, cfg.ssm_state), jnp.float32)
    h_final, _ = jax.lax.scan(step, h0, (split(dt), split(B_ssm), split(xf)))
    return h_final


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def merge_cache_rows(cache: DecodeCache, sub: DecodeCache,
                     rows) -> DecodeCache:
    """Copy ``sub``'s batch rows into ``cache`` at slot indices ``rows``.

    The decode-time join (DESIGN.md §4b): a freshly prefilled request's
    cache rows — KV and mamba conv/ssm state — replace the freed slots of
    the live decode cache.

    Contiguous ``cache``: ``sub`` must have been allocated at the same
    ``max_len``. Paged ``cache`` (``block_tables`` set): ``sub`` is a
    contiguous B=len(rows) cache whose tokens are scattered through each
    destination row's block table — the caller must have allocated enough
    blocks to cover ``sub``'s sequence length, else the overflow lands in
    the trash block. When ``cache.pos`` is a per-row vector the joined
    rows' positions are set from ``sub.pos``; a scalar ``pos`` (lockstep
    batch) is left to the caller.
    """
    idx = jnp.asarray(rows, jnp.int32)

    if cache.block_tables is not None:
        bs = cache.k.shape[2]
        max_blocks = cache.block_tables.shape[1]
        S = sub.k.shape[2]
        positions = jnp.arange(S, dtype=jnp.int32)
        blk = positions // bs
        off = positions % bs                            # (S,)
        phys = cache.block_tables[idx][:, jnp.clip(blk, 0, max_blocks - 1)]
        # out-of-width overflow lands in the trash block (see attention)
        phys = jnp.where((blk < max_blocks)[None, :], phys,
                         attn_mod.TRASH_BLOCK)          # (n, S)
        new = cache._replace(
            k=cache.k.at[:, phys, off].set(sub.k.astype(cache.k.dtype)),
            v=cache.v.at[:, phys, off].set(sub.v.astype(cache.v.dtype)),
            pos=cache.pos.at[idx].set(
                jnp.broadcast_to(sub.pos, idx.shape).astype(jnp.int32)))
        return new

    def put(dst, src):
        if dst is None:
            return None
        return dst.at[:, idx].set(src.astype(dst.dtype))

    new = cache._replace(
        k=put(cache.k, sub.k), v=put(cache.v, sub.v),
        conv=put(cache.conv, sub.conv), ssm=put(cache.ssm, sub.ssm))
    if cache.pos.ndim:
        new = new._replace(
            pos=cache.pos.at[idx].set(
                jnp.broadcast_to(sub.pos, idx.shape).astype(jnp.int32)))
    return new


def decode_step(params, cfg: ModelConfig, token: jax.Array,
                cache: DecodeCache, plan=None, backend=None,
                collect_routing: bool = False
                ) -> Tuple[jax.Array, DecodeCache]:
    """One cache-appending step: a decode token or a prefill chunk.

    token: (B, C) int32 -> (last-position logits (B, V), new cache).
    C == 1 is plain decode; C > 1 appends a chunk at each row's position
    (chunked prefill, paged caches only — mamba state has no chunked
    append yet, so multi-token steps assert attention-only).

    ``cache.pos`` may be a scalar (lockstep) or a (B,) vector (continuous
    batching); either way the returned cache has ``pos + C`` — callers
    that freeze drained rows (the continuous engine) re-pin ``pos``
    before the next step.

    ``backend`` selects the attention kernel backend ("ref" | "pallas" |
    None for auto) — threaded into every layer's ``decode_attention``
    dispatch (DESIGN.md §Kernel backends).

    ``collect_routing`` (MoE only) stacks every layer's router top-k
    ids through the scan and returns them on ``new_cache.route_topk``
    ((L, B*C, k) int32) for the engine's hot-expert replication
    tracker; the field is an OUTPUT only — the incoming cache's value
    is ignored and callers strip it before feeding the cache back in.
    """
    assert cfg.causal
    C = token.shape[1]
    assert C == 1 or not cfg.has_mamba, \
        "chunked append is attention-only (no mamba state chunk step)"
    x = embed_tokens(params, cfg, token)
    if plan is not None and not plan.is_null:
        x = plan.constrain(x, plan.act_btd())
    pos = cache.pos
    collect_routing = collect_routing and cfg.ffn_type == "moe"
    if cfg.is_mla:
        return _mla_decode_step(params, cfg, x, cache, plan, backend,
                                collect_routing)
    flags = _layer_flags(cfg)
    kv = (cache.k, cache.v)
    x, dense_kv = _dense_decode_prefix(params, cfg, x, cache, plan, backend)
    if dense_kv:
        K = cfg.num_dense_layers
        kv = (cache.k[K:], cache.v[K:])

    layers, stacked = split_decode_stacks(cfg, params["layers"], kv)
    xs: Dict[str, Any] = {"lp": layers, "flag": flags,
                          "layer": jnp.arange(flags.shape[0])}
    if cfg.has_mamba:
        xs["conv"] = cache.conv
        xs["ssm"] = cache.ssm

    body = make_decode_body(cfg, plan, pos, stacked, cache.block_tables,
                            backend, prefix_groups=cache.prefix_groups,
                            collect_routing=collect_routing)
    h, ys = _scan(body, x, xs)
    new_cache = cache._replace(pos=pos + C, route_topk=None)
    if cfg.has_attention:
        k, v = ys["k"], ys["v"]
        if dense_kv:
            k, v = _stack_front(dense_kv, (k, v))
        new_cache = new_cache._replace(k=k, v=v)
    if cfg.has_mamba:
        new_cache = new_cache._replace(conv=ys["conv"], ssm=ys["ssm"])
    if collect_routing:
        new_cache = new_cache._replace(route_topk=ys["route"])
    logits = unembed(params, cfg, h[:, -1:, :])
    return logits[:, 0], new_cache


def _dense_decode_prefix(params, cfg: ModelConfig, x, cache: DecodeCache, plan,
                         backend):
    """The leading dense layers of a (GQA) decode step, one at a time, each
    on its own layer of the cache. Returns (x, [(k, v) per layer])."""
    out = []
    flags = cfg.global_layer_flags()
    for i in range(cfg.num_dense_layers):
        lp = _dense_layer(params, i)
        hn = rms_norm(x, lp["ln1"], cfg.norm_eps)
        with jax.named_scope("attn"):
            a, k_c, v_c = attn_mod.decode_attention(
                hn, attn_mod.AttnTemps(**lp["attn"]), cfg, jnp.asarray(flags[i]),
                cache.k[i], cache.v[i], cache.pos, plan,
                block_tables=cache.block_tables, prefix_groups=cache.prefix_groups,
                backend=backend)
        x = x + a
        x = x + _dense_ffn(x, lp, cfg, plan)
        out.append((k_c, v_c))
    return x, out


def _mla_decode_step(params, cfg: ModelConfig, x, cache: DecodeCache, plan,
                     backend, collect_routing: bool):
    """``decode_step`` for multi-head latent attention over the paged
    latent pool: the leading dense layers, then the MoE scan, each layer
    writing its new rows into and attending its own layer of the one pool,
    which the loop carries (no per-layer copy of it out and back)."""
    if cache.block_tables is None:
        raise NotImplementedError(_MLA_PAGED_ONLY)
    if plan is not None and not plan.is_null:
        raise NotImplementedError(
            "multi-head latent attention runs on unsharded plans: its one "
            "latent head does not split over a mesh axis")
    C = x.shape[1]
    pos, tables = cache.pos, cache.block_tables
    K = cfg.num_dense_layers

    def attend(h, lp, layer, pool):
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        with jax.named_scope("attn"):
            a, pool = attn_mod.mla_cached_attention(
                hn, attn_mod.MLATemps(**lp["attn"]), cfg, pool, layer, pos, tables,
                backend=backend)
        return h + a, pool

    h, pool = x, cache.k
    for i in range(K):
        lp = _dense_layer(params, i)
        h, pool = attend(h, lp, i, pool)
        h = h + _dense_ffn(h, lp, cfg, plan)

    layers, stacked = split_decode_stacks(cfg, params["layers"], None)

    def body(carry, per_layer):
        h, pool = carry
        lp = per_layer["lp"]
        if "experts" in stacked:
            with jax.named_scope("experts"):
                lp = {**lp, "moe": {**lp["moe"],
                                    **_take(stacked["experts"], per_layer["layer"])}}
        h, pool = attend(h, lp, per_layer["layer"] + K, pool)
        ffn_out, _aux, route_idx = _ffn_full(h, lp, cfg, plan, backend)
        ys = {"route": route_idx} if collect_routing and route_idx is not None else {}
        return (h + ffn_out, pool), ys

    n = cfg.num_layers - K
    (h, pool), ys = _scan(body, (h, pool), {"lp": layers, "layer": jnp.arange(n)})
    new_cache = cache._replace(k=pool, pos=pos + C, route_topk=ys.get("route"))
    logits = unembed(params, cfg, h[:, -1:, :])
    return logits[:, 0], new_cache


def _take(tree, layer):
    """Layer ``layer`` (traced) of every leaf of a stacked subtree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(
            a, layer, keepdims=False, allow_negative_indices=False),
        tree)


def split_decode_stacks(cfg: ModelConfig, layers, kv):
    """Split the decode scan's stacked inputs: ``(layers, stacked)``.

    The routed experts' weights and the KV pool ``kv`` = ``(k, v)`` leave
    ``layers`` for ``stacked``, which the decode body slices per layer
    under its scopes rather than the scan: the copies then carry the
    ``experts`` / ``kv_write`` names in a device trace. Works on arrays,
    abstract shapes and partition specs alike.
    """
    stacked: Dict[str, Any] = {}
    if cfg.ffn_type == "moe":
        moe = dict(layers["moe"])
        stacked["experts"] = {n: moe.pop(n) for n in moe_mod.EXPERT_LEAVES}
        layers = {**layers, "moe": moe}
    if cfg.has_attention and kv is not None:
        stacked["kv"] = tuple(kv)
    return layers, stacked


def make_decode_body(cfg: ModelConfig, plan, pos, stacked, block_tables=None,
                     backend=None, prefix_groups=None,
                     collect_routing: bool = False):
    """The decode layer-scan body (exposed for the dry-run cost probe).

    ``block_tables`` (shared by every layer — one logical layout per
    request) switches the attention path to the paged layout;
    ``prefix_groups`` (also layer-shared) additionally routes shared
    prefix blocks through their group representative's table (DESIGN.md
    §4d); ``backend`` picks the kernel implementation behind the
    dispatch.

    ``stacked`` (from ``split_decode_stacks``) holds whole-stack leaves
    the body slices at ``per_layer["layer"]`` itself: ``"experts"`` (the
    routed expert weights, merged into ``lp["moe"]``) and ``"kv"`` (the
    K and V pools).
    """

    def body(h, per_layer):
        lp, flag = per_layer["lp"], per_layer["flag"]
        if "experts" in stacked:
            with jax.named_scope("experts"):
                lp = {**lp, "moe": {**lp["moe"],
                                    **_take(stacked["experts"],
                                           per_layer["layer"])}}
        if "kv" in stacked:
            with jax.named_scope("kv_write"):
                k_l, v_l = _take(stacked["kv"], per_layer["layer"])
        ys: Dict[str, Any] = {}
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        outs = []
        if cfg.has_attention:
            with jax.named_scope("attn"):
                w = attn_mod.AttnTemps(**lp["attn"])
                a_out, k_c, v_c = attn_mod.decode_attention(
                    hn, w, cfg, flag, k_l, v_l, pos, plan,
                    block_tables=block_tables, prefix_groups=prefix_groups,
                    backend=backend)
            ys["k"], ys["v"] = k_c, v_c
            outs.append(("attn", a_out))
        if cfg.has_mamba:
            mc = mamba_mod.MambaCache(conv=per_layer["conv"],
                                      ssm=per_layer["ssm"])
            m_out, mc_new = mamba_mod.mamba_decode_step(hn, lp["mamba"],
                                                        cfg, mc)
            ys["conv"], ys["ssm"] = mc_new.conv, mc_new.ssm
            outs.append(("mamba", m_out))
        if cfg.block_type == "hybrid":
            out = 0.5 * (rms_norm(outs[0][1], lp["fuse_norm_attn"],
                                  cfg.norm_eps)
                         + rms_norm(outs[1][1], lp["fuse_norm_mamba"],
                                    cfg.norm_eps))
        else:
            out = outs[0][1]
        if cfg.use_post_norm:
            out = rms_norm(out, lp["ln1_post"], cfg.norm_eps)
        h = h + out
        # decode-time expert compute rides the same seam (grouped matmul)
        ffn_out, _aux, route_idx = _ffn_full(h, lp, cfg, plan, backend)
        h = h + ffn_out
        if collect_routing and route_idx is not None:
            ys["route"] = route_idx
        return h, ys

    return body
