"""Parameter initialization and abstract shapes.

Parameters are stored as a nested dict pytree with every per-layer leaf
STACKED along a leading ``num_layers`` axis so the forward pass can
``lax.scan`` over layers — this keeps the lowered HLO one-layer-sized,
which is what makes 512-device dry-run compiles tractable. Leading dense
layers of an MoE model (``cfg.first_k_dense_replace``) run one at a time
before the scan and are not stacked: ``dense_layers["0"]``, ``["1"]``, ...
each hold one layer's attention, norms and dense FFN of ``cfg.d_ff``; the
``layers`` stack then holds the MoE layers after them.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig, override: Optional[str] = None):
    return jnp.dtype(override or cfg.dtype)


def _attn_shapes(cfg: ModelConfig, lead: tuple) -> Dict[str, Any]:
    """Attention leaves with leading dims ``lead`` ((L,) stacked, () one)."""
    d, hq = cfg.d_model, cfg.num_heads
    if cfg.is_mla:
        r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        return {
            "wq": lead + (d, hq * cfg.head_dim),
            "wkv_a": lead + (d, r + dr),
            "kv_norm": lead + (r,),
            "wkv_b": lead + (r, hq * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": lead + (hq * cfg.v_head_dim, d),
        }
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": lead + (d, hq * hd),
        "wk": lead + (d, hkv * hd),
        "wv": lead + (d, hkv * hd),
        "wo": lead + (hq * hd, d),
    }


def _glu_shapes(d: int, f: int, lead: tuple) -> Dict[str, Any]:
    return {"wi_gate": lead + (d, f), "wi_up": lead + (d, f), "wo": lead + (f, d)}


def param_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    """Abstract shapes (tuples) of every parameter leaf."""
    d, V = cfg.d_model, cfg.vocab_size
    K = cfg.num_dense_layers
    L = cfg.num_layers - K
    shapes: Dict[str, Any] = {"embed": (V, d), "final_norm": (d,)}
    if not cfg.tie_embeddings:
        shapes["lm_head"] = (d, V)
    if cfg.frontend != "none":
        shapes["frontend_proj"] = (cfg.frontend_dim, d)

    layers: Dict[str, Any] = {"ln1": (L, d)}
    if cfg.use_post_norm:
        shapes_post = {"ln1_post": (L, d), "ln2_post": (L, d)}
        layers.update(shapes_post)
    if cfg.has_attention:
        layers["attn"] = _attn_shapes(cfg, (L,))
    if cfg.has_mamba:
        di, n, r, cw = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv
        layers["mamba"] = {
            "in_proj": (L, d, 2 * di),
            "conv_w": (L, cw, di),
            "conv_b": (L, di),
            "x_proj": (L, di, r + 2 * n),
            "dt_w": (L, r, di),
            "dt_b": (L, di),
            "A_log": (L, di, n),
            "D": (L, di),
            "out_proj": (L, di, d),
        }
    if cfg.block_type == "hybrid":
        layers["fuse_norm_attn"] = (L, d)
        layers["fuse_norm_mamba"] = (L, d)
    if cfg.ffn_type == "dense":
        layers["ln2"] = (L, d)
        glu = cfg.activation in ("silu", "gelu")
        f = cfg.d_ff
        if glu:
            layers["ffn"] = _glu_shapes(d, f, (L,))
        else:
            layers["ffn"] = {"wi": (L, d, f), "wo": (L, f, d)}
    elif cfg.ffn_type == "moe":
        layers["ln2"] = (L, d)
        E, f, sf = cfg.n_routed_experts, cfg.moe_d_ff, cfg.shared_d_ff
        moe: Dict[str, Any] = {
            "router": (L, d, E),
            "wi_gate": (L, E, d, f),
            "wi_up": (L, E, d, f),
            "wo": (L, E, f, d),
        }
        if cfg.n_shared_experts:
            s = cfg.n_shared_experts
            moe["shared_wi_gate"] = (L, d, sf * s)
            moe["shared_wi_up"] = (L, d, sf * s)
            moe["shared_wo"] = (L, sf * s, d)
        layers["moe"] = moe
    shapes["layers"] = layers
    if K:
        shapes["dense_layers"] = {
            str(i): {"ln1": (d,), "ln2": (d,), "attn": _attn_shapes(cfg, ()),
                     "ffn": _glu_shapes(d, cfg.d_ff, ())}
            for i in range(K)}
    return shapes


def _sanitize(shapes, specs, plan):
    """Drop sharding on any dim whose size doesn't divide the axis size
    (e.g. hymba's vocab 32001, hubert's 504 against a 16-way axis)."""
    def fix(shape, spec):
        if not isinstance(spec, P):
            return spec
        new = []
        for dim, ax in zip(shape, tuple(spec) + (None,) * len(shape)):
            if ax is None:
                new.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axes:
                size *= plan.axis_size(a)
            new.append(ax if size and dim % size == 0 else None)
        return P(*new)

    return jax.tree.map(fix, shapes, specs,
                        is_leaf=lambda x: isinstance(x, tuple))


def fsdp_pspecs(cfg: ModelConfig, plan) -> Dict[str, Any]:
    """ZeRO-3: shard each leaf's largest divisible non-layer dim over all
    mesh axes (weights gathered per layer inside the scan)."""
    axes = tuple(plan.mesh.axis_names)
    total = 1
    for a in axes:
        total *= plan.axis_size(a)

    def spec_for(shape):
        # skip the stacked-layer dim (index 0 for per-layer leaves) when
        # picking the shard dim; scalars/1-dim-too-small stay replicated
        best, best_dim = None, 0
        for i, dim in enumerate(shape):
            if dim % total == 0 and dim > best_dim:
                best, best_dim = i, dim
        out = [None] * len(shape)
        if best is not None:
            out[best] = axes
        return P(*out)

    shapes = param_shapes(cfg)
    return jax.tree.map(spec_for, shapes,
                        is_leaf=lambda x: isinstance(x, tuple))


def param_pspecs(cfg: ModelConfig, plan) -> Dict[str, Any]:
    """PartitionSpecs matching ``param_shapes`` for a ShardingPlan."""
    if getattr(plan, "fsdp", False):
        return fsdp_pspecs(cfg, plan)
    tp = plan.ffn_tp_axis
    at = plan.attn_tp_axis if plan.attn_mode == "tp_heads" else None
    kv_ok = (at is not None
             and cfg.num_kv_heads % plan.axis_size(at) == 0)
    ep = plan.ep_axis

    specs: Dict[str, Any] = {
        # embedding sharded over vocab on the model axis (all-gather at use)
        "embed": P(tp, None),
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, tp)
    if cfg.frontend != "none":
        specs["frontend_proj"] = P(None, None)

    layers: Dict[str, Any] = {"ln1": P(None, None)}
    if cfg.use_post_norm:
        layers["ln1_post"] = P(None, None)
        layers["ln2_post"] = P(None, None)
    if cfg.is_mla:
        # one latent head serves every q head: nothing of it splits
        attn = {n: P(None, None, None) for n in ("wq", "wkv_a", "wkv_b", "wo")}
        attn["kv_norm"] = P(None, None)
        layers["attn"] = attn
    elif cfg.has_attention:
        layers["attn"] = {
            "wq": P(None, None, at),
            "wk": P(None, None, at if kv_ok else None),
            "wv": P(None, None, at if kv_ok else None),
            "wo": P(None, at, None),
        }
    if cfg.has_mamba:
        mtp = tp  # shard d_inner on the model axis
        layers["mamba"] = {
            "in_proj": P(None, None, mtp),
            "conv_w": P(None, None, mtp),
            "conv_b": P(None, mtp),
            "x_proj": P(None, mtp, None),
            "dt_w": P(None, None, mtp),
            "dt_b": P(None, mtp),
            "A_log": P(None, mtp, None),
            "D": P(None, mtp),
            "out_proj": P(None, mtp, None),
        }
    if cfg.block_type == "hybrid":
        layers["fuse_norm_attn"] = P(None, None)
        layers["fuse_norm_mamba"] = P(None, None)
    if cfg.ffn_type == "dense":
        layers["ln2"] = P(None, None)
        glu = cfg.activation in ("silu", "gelu")
        if glu:
            layers["ffn"] = {"wi_gate": P(None, None, tp),
                             "wi_up": P(None, None, tp),
                             "wo": P(None, tp, None)}
        else:
            layers["ffn"] = {"wi": P(None, None, tp),
                             "wo": P(None, tp, None)}
    elif cfg.ffn_type == "moe":
        layers["ln2"] = P(None, None)
        if ep is not None:
            moe = {
                "router": P(None, None, None),
                "wi_gate": P(None, ep, None, None),
                "wi_up": P(None, ep, None, None),
                "wo": P(None, ep, None, None),
            }
        else:
            moe = {
                "router": P(None, None, None),
                "wi_gate": P(None, None, None, tp),
                "wi_up": P(None, None, None, tp),
                "wo": P(None, None, tp, None),
            }
        if cfg.n_shared_experts:
            moe["shared_wi_gate"] = P(None, None, tp)
            moe["shared_wi_up"] = P(None, None, tp)
            moe["shared_wo"] = P(None, tp, None)
        layers["moe"] = moe
    specs["layers"] = layers
    if cfg.num_dense_layers:
        # one layer's leaves: the stacked specs without their layer dim
        one = jax.tree.map(lambda p: P(*tuple(p)[1:]), layers["attn"],
                           is_leaf=lambda x: isinstance(x, P))
        specs["dense_layers"] = {
            str(i): {"ln1": P(None), "ln2": P(None), "attn": one,
                     "ffn": {"wi_gate": P(None, tp), "wi_up": P(None, tp),
                             "wo": P(tp, None)}}
            for i in range(cfg.num_dense_layers)}
    return _sanitize(param_shapes(cfg), specs, plan)


def abstract_params(cfg: ModelConfig, dtype: Optional[str] = None):
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    dt = _dtype(cfg, dtype)

    def to_sds(shape):
        return jax.ShapeDtypeStruct(shape, dt)

    return jax.tree.map(to_sds, param_shapes(cfg),
                        is_leaf=lambda x: isinstance(x, tuple))


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: Optional[str] = None, shardings=None) -> Params:
    """Random initialization from ``key``.

    ``shardings`` (a ``Sharding``, or a tree of them matching
    ``param_shapes``) creates every leaf already in place inside one jit,
    so a model larger than one device is never materialized whole on
    device 0. Without it the leaves are made eagerly on the default device.
    """
    dt = _dtype(cfg, dtype)
    shapes = param_shapes(cfg)
    paths, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        keys = jax.random.split(key, len(paths))
        leaves = []
        for (path, shape), k in zip(paths, keys):
            name = str(path[-1].key) if hasattr(path[-1], "key") \
                else str(path[-1])
            if "norm" in name or name.startswith("ln"):
                leaves.append(jnp.ones(shape, dt))
            elif name == "A_log":
                # mamba1: A = -exp(A_log), init A_log = log(1..N)
                n = shape[-1]
                a = jnp.tile(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                             shape[:-1] + (1,))
                leaves.append(a.astype(dt))
            elif name == "D":
                leaves.append(jnp.ones(shape, dt))
            elif name in ("conv_b", "dt_b"):
                leaves.append(jnp.zeros(shape, dt))
            elif name == "embed":
                leaves.append(jax.random.normal(k, shape, dt) * 0.02)
            else:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                std = 1.0 / math.sqrt(max(fan_in, 1))
                leaves.append(jax.random.normal(k, shape, dt) * std)
        return jax.tree.unflatten(treedef, leaves)

    if shardings is None:
        return build(key)
    return jax.jit(build, out_shardings=shardings)(key)


def param_shardings(cfg: ModelConfig, plan) -> Dict[str, Any]:
    """``NamedSharding`` tree for ``param_pspecs`` on the plan's mesh."""
    return jax.tree.map(plan.sharding, param_pspecs(cfg, plan),
                        is_leaf=lambda x: isinstance(x, P))


def count_params(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
