"""Mixture-of-Experts: router, capacity-based dispatch, shared experts.

Three execution modes, selected by the ShardingPlan (i.e. by the HAP
strategy for the Expert module — the paper's central object of study):

  local — single device (CPU smoke tests). Dispatch + dense per-expert GEMM.
  tp    — expert weights sharded on the intermediate dim over the TP axis;
          every device processes every token of every expert; combine is a
          psum inserted by SPMD (this is the paper's "TP" expert strategy,
          all-reduce communication pattern).
  ep    — experts sharded over the EP axis; tokens are exchanged with
          all_to_all inside shard_map (the paper's "EP" strategy).

Dispatch is GShard-style with a static capacity
``C = ceil(T * top_k / E * capacity_factor)`` per expert: tokens beyond an
expert's capacity are dropped (standard in inference engines; the HAP cost
model's 2x activation upper bound for EP imbalance mirrors the paper).
The dispatch is gather-based (an index map scattered once, then a single
gather) to avoid materializing a (T*k, d) replica of the activations.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.sharding.specs import ExpertReplication  # noqa: F401 (re-export)
from .common import activation_fn, glu_ffn


class MoEOut(NamedTuple):
    y: jax.Array          # (B, S, d)
    aux_loss: jax.Array   # scalar load-balance loss
    # router's top-k expert ids, (B*S, top_k) int32 — the engine's
    # routing-frequency tracker feeds on these (hot-expert replication)
    route_idx: Optional[jax.Array] = None


# the routed experts' weight leaves under ``moe`` (stacked (L, E, ...))
EXPERT_LEAVES = ("wi_gate", "wi_up", "wo")


def capacity(num_tokens: int, cfg: ModelConfig) -> int:
    c = math.ceil(num_tokens * cfg.top_k / cfg.n_routed_experts
                  * cfg.capacity_factor)
    return max(8, int(math.ceil(c / 8) * 8))


def pipeline_chunks(C_loc: int, ep_size: int, knob: int = 0) -> int:
    """Resolve the EP pipeline depth K for a local capacity ``C_loc``.

    ``knob`` is ``ShardingPlan.moe_pipeline``: 1 pins the serial path,
    K>=2 forces that many capacity slabs (clamped to C_loc so no slab is
    empty), and 0 picks automatically — the deepest K in {4, 2} whose
    slabs keep the 8-row capacity granule (``capacity`` rounds C to
    multiples of 8; thinner slabs just add exchange launches without
    compute to hide them behind), serial when there is no all_to_all to
    overlap (ep_size 1). The latency model mirrors this rule
    (``latency.ep_pipeline_chunks``) so the ILP prices what runs.
    """
    if knob == 1:
        return 1
    if knob >= 2:
        return min(knob, max(C_loc, 1))
    if ep_size <= 1:
        return 1
    for k in (4, 2):
        if C_loc >= 8 * k:
            return k
    return 1


@jax.named_scope("router")
def route(x_flat: jax.Array, router_w: jax.Array, cfg: ModelConfig):
    """Top-k routing. x_flat: (T, d) -> gates (T,k), idx (T,k), aux_loss."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss: E * sum(frac_tokens * frac_probs)
    E = cfg.n_routed_experts
    me = jnp.mean(probs, axis=0)
    one_hot_top1 = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot_top1, axis=0)
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def make_dispatch(idx: jax.Array, gates: jax.Array, E: int, C: int):
    """Scatter coordinates with capacity dropping.

    Returns (flat_expert (T*k,), pos_in_expert (T*k,), keep (T*k,),
    flat_gates (T*k,)). Entries with pos_in_expert >= C are dropped.
    """
    flat_expert = idx.reshape(-1)                              # (T*k,)
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)   # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                       # (T*k, E)
    pos_in_expert = jnp.sum(pos * onehot, axis=-1)             # (T*k,)
    keep = pos_in_expert < C
    return flat_expert, pos_in_expert, keep, gates.reshape(-1)


def dispatch(x_flat, flat_expert, pos_in_expert, E: int, C: int):
    """Gather-based scatter of tokens into (E, C, d) expert buffers."""
    T = x_flat.shape[0]
    k = flat_expert.shape[0] // T
    token_id = jnp.arange(T * k, dtype=jnp.int32) // k
    # sentinel T = "empty slot"; overflow entries dropped by mode="drop"
    idx_map = jnp.full((E, C), T, jnp.int32)
    idx_map = idx_map.at[flat_expert, pos_in_expert].set(token_id,
                                                         mode="drop")
    x_pad = jnp.concatenate(
        [x_flat, jnp.zeros((1, x_flat.shape[-1]), x_flat.dtype)], axis=0)
    return x_pad[idx_map], idx_map                              # (E, C, d)


def combine(y_buf, flat_expert, pos_in_expert, keep, flat_gates, T: int):
    """Gather expert outputs back: y_buf (E, C, d) -> (T, d)."""
    k = flat_expert.shape[0] // T
    safe_pos = jnp.where(keep, pos_in_expert, 0)
    gathered = y_buf[flat_expert, safe_pos]                    # (T*k, d)
    gathered = gathered * (flat_gates * keep)[:, None].astype(y_buf.dtype)
    return jnp.sum(gathered.reshape(T, k, -1), axis=1)


def replica_coords(flat_expert, pos_in_expert, rep: ExpertReplication):
    """(expert id, pos within expert) -> (slot id, pos within replica).

    Token copy ``p`` of expert ``e`` lands on replica ``p % degree(e)``
    inside the expert's contiguous slot block — the deterministic
    round-robin "least-loaded" choice (replica loads differ by at most
    one token), implemented as two table lookups so it stays a cheap
    gather inside the jit.
    """
    degrees = jnp.asarray(rep.degrees, jnp.int32)
    offsets = jnp.asarray(rep.expert_offsets(), jnp.int32)
    deg = degrees[flat_expert]
    slot = offsets[flat_expert] + pos_in_expert % deg
    return slot, pos_in_expert // deg


def slot_weights(w, rep: ExpertReplication):
    """Gather per-slot expert weights: leading dim E -> total_slots.

    Works on dense (E, ...) arrays and on resident ``QuantizedExpert``
    pytrees alike (every leaf shares the leading expert dim). The
    gather happens in-jit, so replicas never exist as separate host
    copies — a replica-set change is just a new index table.
    """
    sl = jnp.asarray(rep.slot_to_expert(), jnp.int32)
    return jax.tree_util.tree_map(lambda a: a[sl], w)


def _active_replication(plan) -> Optional[ExpertReplication]:
    rep = getattr(plan, "replication", None) if plan is not None else None
    if rep is None or rep.is_identity:
        return None
    return rep


def expert_ffn(buf: jax.Array, wi_gate: jax.Array, wi_up: jax.Array,
               wo: jax.Array, act_name: str, *, plan=None,
               backend=None) -> jax.Array:
    """(E, C, d) x (E, d, f)^2 x (E, f, d) -> (E, C, d).

    Every per-expert GEMM dispatches through the grouped-matmul seam
    (``repro.kernels.ops.grouped_matmul``, DESIGN.md §4c): the ``ref``
    backend is the einsum XLA partitions under the plan's constraints;
    ``pallas`` runs the grouped kernel — per d_ff shard under shard_map
    when a TP ``plan`` resolves ``expert_kernel_axes`` (column-parallel
    wi_gate/wi_up, row-parallel wo with a psum combine). A sharded plan
    whose d_ff does not divide the axis pins ``ref`` (a bare Pallas call
    cannot be SPMD-partitioned).
    """
    act = activation_fn(act_name)
    axes = None
    if plan is not None and not plan.is_null:
        axes = plan.expert_kernel_axes(wi_gate.shape[-1])
        if axes is None:
            backend = kernel_ops.KernelBackend.REF
    gate = kernel_ops.grouped_matmul(buf, wi_gate, shard_axes=axes,
                                     sharded_dim="out", backend=backend)
    up = kernel_ops.grouped_matmul(buf, wi_up, shard_axes=axes,
                                   sharded_dim="out", backend=backend)
    return kernel_ops.grouped_matmul(act(gate) * up, wo, shard_axes=axes,
                                     sharded_dim="in", backend=backend)


# ---------------------------------------------------------------------------
def _moe_local(x_flat, moe_p, cfg: ModelConfig, backend=None, rep=None):
    T = x_flat.shape[0]
    E = cfg.n_routed_experts
    C = capacity(T, cfg)
    gates, idx, aux = route(x_flat, moe_p["router"], cfg)
    fe, pe, keep, fg = make_dispatch(idx, gates, E, C)
    wig, wiu, wo = moe_p["wi_gate"], moe_p["wi_up"], moe_p["wo"]
    if rep is not None:
        fe, pe = replica_coords(fe, pe, rep)
        keep = pe < C  # per-SLOT capacity: hot experts hold degree*C
        E = rep.total_slots
        wig, wiu, wo = (slot_weights(w, rep) for w in (wig, wiu, wo))
    buf, _ = dispatch(x_flat, fe, pe, E, C)
    y_buf = expert_ffn(buf, wig, wiu, wo, cfg.activation, backend=backend)
    y = combine(y_buf, fe, pe, keep, fg, T)
    return y, aux, idx


def _moe_ep_shardmap(x_flat, moe_p, cfg: ModelConfig, plan, backend=None):
    """EP: experts sharded over plan.ep_axis; all_to_all token exchange.

    x_flat is (T, d) sharded over the DP axes; router weights replicated;
    expert weights (E, d, 2f)/(E, f, d) sharded on E.
    """
    mesh = plan.mesh
    ep_ax = plan.ep_axis
    E = cfg.n_routed_experts
    # Token sharding for dispatch: split over BOTH the DP axes and the EP
    # axis when divisible (each device dispatches T/(dp*ep) tokens — no
    # redundant expert compute); fall back to DP-only (tokens replicated
    # within EP groups — correct but redundant, only hit by tiny decode
    # batches) when T doesn't divide.
    T = x_flat.shape[0]
    dp_size = 1
    for a in plan.dp_axes:
        dp_size *= plan.axis_size(a)
    ep_size = plan.axis_size(ep_ax)
    if T % (dp_size * ep_size) == 0:
        tok_axes = tuple(plan.dp_axes) + (ep_ax,)
    elif dp_size > 1 and T % dp_size == 0:
        tok_axes = tuple(plan.dp_axes)
    else:
        tok_axes = ()
    dp_spec = P(tok_axes or None, None)

    # Hot-expert replication: gather the per-slot weight view in-jit
    # (dense or QuantizedExpert leaves alike) and shard the SLOT axis
    # over EP — hot experts then own replica slots on several devices,
    # and the affinity-ordered slot layout keeps co-firing experts in
    # the same shard. Needs total_slots % ep == 0; otherwise serve
    # unreplicated (a planner with `align=ep` never hits the fallback).
    rep = _active_replication(plan)
    if rep is not None and rep.total_slots % ep_size:
        rep = None
    n_slots = rep.total_slots if rep is not None else E
    wig, wiu, wo = moe_p["wi_gate"], moe_p["wi_up"], moe_p["wo"]
    if rep is not None:
        wig, wiu, wo = (slot_weights(w, rep) for w in (wig, wiu, wo))

    def w_spec(w):
        n = w.packed.ndim if isinstance(w, kernel_ops.QuantizedExpert) \
            else w.ndim
        return P(ep_ax, *([None] * (n - 1)))

    def local_fn(xl, router_w, wig_l, wiu_l, wo_l):
        # xl: (T_loc, d) — this device's dispatch shard.
        T_loc = xl.shape[0]
        C_loc = capacity(T_loc, cfg)
        gates, idx, aux = route(xl, router_w, cfg)
        fe, pe, keep, fg = make_dispatch(idx, gates, E, C_loc)
        if rep is not None:
            fe, pe = replica_coords(fe, pe, rep)
            keep = pe < C_loc
        buf, _ = dispatch(xl, fe, pe, n_slots, C_loc)     # (S, C_loc, d)
        # exchange + expert FFN, micro-batch pipelined over K capacity
        # slabs (each slab: dispatch all_to_all -> grouped FFN -> combine
        # all_to_all, slab i+1's exchange overlapping slab i's compute).
        # Routing and capacity were assigned on the FULL local batch
        # above, so K only reshapes the schedule, never the semantics.
        # Already inside the EP shard_map: slabs are device-local, so the
        # grouped kernel runs directly on them (plan=None at the seam).
        K = pipeline_chunks(C_loc, ep_size, plan.moe_pipeline)
        y_buf = kernel_ops.pipelined_ep_ffn(
            buf,
            lambda b: expert_ffn(b, wig_l, wiu_l, wo_l, cfg.activation,
                                 backend=backend),
            ep_axis=ep_ax, chunks=K)                      # (S, C_loc, d)
        y = combine(y_buf, fe, pe, keep, fg, T_loc)
        return y, jax.lax.pmean(aux, ep_ax), idx

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(dp_spec, P(None, None), w_spec(wig), w_spec(wiu),
                  w_spec(wo)),
        out_specs=(dp_spec, P(), P(tok_axes or None, None)),
        check_vma=False)
    y, aux, idx = fn(x_flat, moe_p["router"], wig, wiu, wo)
    return y, jnp.mean(aux), idx


def _moe_tp(x_flat, moe_p, cfg: ModelConfig, plan, backend=None):
    """TP: expert intermediate dim sharded — the grouped kernel runs per
    d_ff shard (``expert_ffn`` shard_map); on the ``ref`` backend SPMD
    inserts the all-reduce for the einsum exactly as before."""
    T = x_flat.shape[0]
    E = cfg.n_routed_experts
    C = capacity(T, cfg)
    gates, idx, aux = route(x_flat, moe_p["router"], cfg)
    fe, pe, keep, fg = make_dispatch(idx, gates, E, C)
    wig, wiu, wo = moe_p["wi_gate"], moe_p["wi_up"], moe_p["wo"]
    rep = _active_replication(plan)
    if rep is not None:
        fe, pe = replica_coords(fe, pe, rep)
        keep = pe < C
        E = rep.total_slots
        wig, wiu, wo = (slot_weights(w, rep) for w in (wig, wiu, wo))
    buf, _ = dispatch(x_flat, fe, pe, E, C)
    buf = plan.constrain(buf, P(None, plan.dp, None))
    y_buf = expert_ffn(buf, wig, wiu, wo, cfg.activation, plan=plan,
                       backend=backend)
    y_buf = plan.constrain(y_buf, P(None, plan.dp, None))
    y = combine(y_buf, fe, pe, keep, fg, T)
    return y, aux, idx


def apply_moe(x: jax.Array, moe_p: Dict[str, Any], cfg: ModelConfig,
              plan, backend=None) -> MoEOut:
    """x: (B, S, d) -> MoEOut. Routed experts + optional shared experts.

    ``backend`` selects the grouped-matmul kernel path for the expert
    FFNs (DESIGN.md §4c) — threaded from the engine like the attention
    backend, so decode-time expert compute joins the kernel seam.

    When the plan carries an ``ExpertReplication``, token copies are
    routed to replica slots (round-robin over each expert's replicas)
    — token-identical to unreplicated serving whenever capacity drops
    don't bind, since gates never change and replicas share weights.
    """
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)

    # device-trace names: "experts" (dispatch, expert FFNs, combine) with
    # "router" inside it (``route``), and "shared_experts"
    with jax.named_scope("experts"):
        if plan is None or plan.is_null:
            y, aux, idx = _moe_local(x_flat, moe_p, cfg, backend=backend,
                                     rep=_active_replication(plan))
        elif plan.ffn_mode == "ep" and plan.ep_axis is not None:
            y, aux, idx = _moe_ep_shardmap(x_flat, moe_p, cfg, plan,
                                           backend=backend)
        else:
            y, aux, idx = _moe_tp(x_flat, moe_p, cfg, plan, backend=backend)

    if cfg.n_shared_experts:
        with jax.named_scope("shared_experts"):
            y_shared = glu_ffn(x_flat, moe_p["shared_wi_gate"],
                               moe_p["shared_wi_up"], moe_p["shared_wo"],
                               cfg.activation)
        y = y + y_shared
    return MoEOut(y.reshape(B, S, d), aux * cfg.router_aux_loss_coef,
                  idx)
