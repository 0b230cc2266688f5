"""Per-layer cost probes for the roofline analysis.

XLA's ``cost_analysis()`` counts each *unique computation* once — a
lax.scan body (and even N unrolled calls to a shared computation) shows up
with multiplicity 1. The dry-run therefore compiles the layer-scan BODY
functions standalone, under the same mesh/shardings as inside the scan,
and scales: ``total = c_full + (num_layers - 1) * c_body``.

Probe functions per kind:
  train   — vjp through jax.checkpoint(layer_full): fwd + remat recompute
            + bwd, exactly the per-layer work of the rematerialized
            training scan.
  prefill — make_prefill_body (includes KV collection / mamba states).
  decode  — make_decode_body (includes cache update + cache-length attn).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.io import INPUT_SHAPES
from repro.models.params import abstract_params, param_pspecs
from repro.models import transformer as T
from repro.launch import roofline


def _strip_l(tree):
    """Drop the leading stacked-layer dim from shapes/specs."""
    def fix(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape[1:], x.dtype)
        if isinstance(x, P):
            return P(*tuple(x)[1:])
        return x
    return jax.tree.map(fix, tree,
                        is_leaf=lambda x: isinstance(x, (P,
                                                         jax.ShapeDtypeStruct)))


def _one_l(tree):
    """Cut the leading stacked-layer dim of shapes to one layer."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((1,) + x.shape[1:], x.dtype), tree)


def _named(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def probe_layer_costs(cfg, shape_name: str, mesh, plan) -> roofline.Costs:
    seq, batch, kind = INPUT_SHAPES[shape_name]
    ap_stack = abstract_params(cfg)["layers"]
    ps_stack = param_pspecs(cfg, plan)["layers"]
    ap_layer, ps_layer = _strip_l(ap_stack), _strip_l(ps_stack)
    dt = jnp.dtype(cfg.dtype)

    if cfg.frontend == "vision" and kind != "decode":
        n_text = max(seq - cfg.num_patches, 16)
        S = cfg.num_patches + n_text
    else:
        S = seq
    act_spec = plan.act_btd()
    flag = True

    if kind in ("train", "prefill"):
        x = jax.ShapeDtypeStruct((batch, S, cfg.d_model), dt)
        if kind == "train":
            def probe(lp, xx, ct):
                def f(p, h):
                    y, _, aux = T.layer_full(h, p, flag, cfg, plan)
                    return y, aux
                f = jax.checkpoint(f)
                (y, aux), vjp = jax.vjp(f, lp, xx)
                gl, gx = vjp((ct, jnp.ones((), jnp.float32)))
                return y, gl, gx
            args = (ap_layer, x, x)
            in_sh = (_named(mesh, ps_layer), NamedSharding(mesh, act_spec),
                     NamedSharding(mesh, act_spec))
        else:
            body = T.make_prefill_body(cfg, plan)

            def probe(lp, xx):
                carry = (xx, jnp.zeros((), jnp.float32))
                (h, aux), ys = body(carry, (lp, jnp.asarray(flag)))
                return h, ys
            args = (ap_layer, x)
            in_sh = (_named(mesh, ps_layer), NamedSharding(mesh, act_spec))
    else:  # decode
        x = jax.ShapeDtypeStruct((batch, 1, cfg.d_model), dt)
        # the engine's decode body over a stack of one layer, at index 0
        kv = kv_sh = None
        if cfg.has_attention:
            kv_dt = jnp.dtype(cfg.kv_cache_dtype) if cfg.kv_cache_dtype \
                else dt
            k = jax.ShapeDtypeStruct(
                (1, batch, seq, cfg.num_kv_heads, cfg.head_dim), kv_dt)
            kv, kv_sh = (k, k), (plan.kv_cache_spec(),) * 2
        lp, stacked = T.split_decode_stacks(cfg, _one_l(ap_stack), kv)
        lp_sh, stacked_sh = T.split_decode_stacks(cfg, ps_stack, kv_sh)
        per_layer: Dict[str, Any] = {
            "lp": _strip_l(lp), "flag": jax.ShapeDtypeStruct((), bool),
            "layer": jax.ShapeDtypeStruct((), jnp.int32)}
        sh: Dict[str, Any] = {"lp": _strip_l(lp_sh), "flag": P(),
                              "layer": P()}
        if cfg.has_mamba:
            per_layer["conv"] = jax.ShapeDtypeStruct(
                (batch, cfg.ssm_conv - 1, cfg.ssm_d_inner), dt)
            per_layer["ssm"] = jax.ShapeDtypeStruct(
                (batch, cfg.ssm_d_inner, cfg.ssm_state), jnp.float32)
            sh["conv"] = P(*tuple(plan.conv_cache_spec())[1:])
            sh["ssm"] = P(*tuple(plan.ssm_cache_spec())[1:])
        pos = jax.ShapeDtypeStruct((), jnp.int32)

        def probe(pl, st, xx, pos_):
            body = T.make_decode_body(cfg, plan, pos_, st)
            return body(xx, pl)
        args = (per_layer, stacked, x, pos)
        dec_spec = P(plan.dp, None, None)
        in_sh = (_named(mesh, sh), _named(mesh, stacked_sh),
                 NamedSharding(mesh, dec_spec), NamedSharding(mesh, P()))

    with mesh:
        compiled = jax.jit(probe, in_shardings=in_sh).lower(*args).compile()
    return roofline.extract_costs(compiled)
