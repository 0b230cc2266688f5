"""Sharding plans: how a HAP strategy maps onto a fixed TPU mesh.

The paper picks parallelism *degrees* on a flat GPU node; on a TPU pod the
mesh shape is fixed, so a strategy becomes an *assignment of tensor
dimensions to mesh axes*. A ``ShardingPlan`` carries that assignment and
hands out ``PartitionSpec``s to the model code, which only ever calls
``plan.pspec(...)`` / ``plan.constrain(...)`` — with a null plan (no mesh)
everything degenerates to unsharded single-device execution, which is what
the CPU smoke tests use.

Two attention modes (see DESIGN.md §5):
  - ``tp_heads``   — q/o weights sharded over heads on the TP axis; k/v
                     sharded too when ``num_kv_heads % tp == 0`` else
                     replicated (transient K/V small). Decode KV cache
                     sharded over heads when divisible, else over sequence.
  - ``replicated`` — attention weights replicated (used when the head count
                     does not divide the axis, e.g. hymba's 25 heads, or when
                     HAP selects attention-DP); the model axis then only
                     parallelizes the FFN / expert / mamba side.

Expert modes: ``tp`` (expert d_ff sharded on TP axis, psum combine) or
``ep`` (expert dim sharded on the EP axis, all_to_all dispatch inside
shard_map).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple  # noqa: F401

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

@dataclasses.dataclass(frozen=True)
class KernelShardAxes:
    """Plan -> shard_map axis resolution for the kernel seam (DESIGN.md §4c).

    ``axis`` is the mesh axis the kernel-sharded dimension maps to
    (attention heads for the decode/prefill attention kernels, expert
    d_ff for the grouped matmuls). ``repro.kernels.ops`` wraps its Pallas
    call in a ``shard_map`` over ``mesh`` with this axis on the sharded
    dim and everything else replicated, so each device runs the fused
    kernel on its own shard — the plans the ILP planner emits execute
    the fast path instead of falling back to the jnp reference.
    """
    mesh: Mesh
    axis: str

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]


@dataclasses.dataclass(frozen=True)
class ExpertReplication:
    """Replica-aware expert placement (hot-expert replication).

    ``degrees[e]`` is the replica count of expert ``e`` (>= 1);
    ``order`` is a permutation of expert ids giving the slot layout —
    expert ``order[0]``'s replica block first, then ``order[1]``'s, and
    so on. The replication planner orders experts by inter-layer
    co-fire affinity so experts that fire together land in the same
    EP slot-axis shard (cutting all2all fan-out); dispatch maps token
    copy ``p`` of expert ``e`` to replica ``p % degrees[e]`` inside the
    expert's contiguous slot block, which both balances replica load
    deterministically and keeps the remap a cheap gather.

    Frozen + tuple-typed so a plan carrying one stays hashable (jit
    cache keys, ``_fn_cache`` entries) — a replica-set change is a NEW
    plan and therefore a re-trace, which is exactly the Eq.-6
    transition semantics the engine's rebalance hook piggybacks on.
    """
    degrees: Tuple[int, ...]
    order: Tuple[int, ...] = ()

    def __post_init__(self):
        if not self.order:
            object.__setattr__(self, "order",
                               tuple(range(len(self.degrees))))
        if sorted(self.order) != list(range(len(self.degrees))):
            raise ValueError(f"order {self.order} is not a permutation")
        if any(d < 1 for d in self.degrees):
            raise ValueError(f"degrees must be >= 1, got {self.degrees}")

    @property
    def n_experts(self) -> int:
        return len(self.degrees)

    @property
    def total_slots(self) -> int:
        return sum(self.degrees)

    @property
    def is_identity(self) -> bool:
        return all(d == 1 for d in self.degrees) and \
            self.order == tuple(range(len(self.degrees)))

    def slot_to_expert(self) -> Tuple[int, ...]:
        out = []
        for e in self.order:
            out.extend([e] * self.degrees[e])
        return tuple(out)

    def expert_offsets(self) -> Tuple[int, ...]:
        """Slot index of each expert's first replica (indexed by expert id)."""
        offsets = [0] * len(self.degrees)
        pos = 0
        for e in self.order:
            offsets[e] = pos
            pos += self.degrees[e]
        return tuple(offsets)


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    mesh: Optional[Mesh] = None
    # axis-name assignments (None = unused)
    dp_axes: Tuple[str, ...] = ()          # batch axes ("pod","data") / ("data",)
    attn_mode: str = "tp_heads"            # tp_heads | replicated
    attn_tp_axis: Optional[str] = None     # heads axis ("model")
    kv_shard: str = "heads"                # heads | seq | none (cache layout)
    ffn_mode: str = "tp"                   # tp | ep  (experts; dense FFN: tp)
    ffn_tp_axis: Optional[str] = None
    ep_axis: Optional[str] = None
    seq_axis: Optional[str] = None         # sequence sharding for long-context
    # Megatron-style sequence parallelism: residual-stream activations
    # (B, S, d) live sequence-sharded on the TP axis between layers, so
    # per-layer saved activations shrink by |tp| and the per-sublayer
    # all-reduce becomes reduce-scatter + all-gather. Off for decode (S=1).
    seq_shard_acts: bool = False
    # FSDP/ZeRO-3: every parameter (and optimizer moment) sharded over ALL
    # mesh axes; weights are all-gathered per layer inside the scan and
    # gradients reduce-scattered — pure data-parallel compute. This is the
    # training-side analog of HAP's attention-DP strategy (beyond-paper,
    # see EXPERIMENTS §Perf).
    fsdp: bool = False
    # Hot-expert replication: when set, MoE dispatch routes token copies
    # to replica *slots* (see ExpertReplication) instead of raw expert
    # ids. Part of the frozen plan on purpose: a replica-set change is a
    # plan change, so the engine's jit cache and transition machinery
    # treat a rebalance exactly like any other plan switch.
    replication: Optional[ExpertReplication] = None
    # EP micro-batch pipelining (EPS-MoE style): the dispatch buffer is
    # split into K capacity chunks so each chunk's all_to_all overlaps
    # the previous chunk's expert FFN (models/moe.py). 0 = auto (pick K
    # from the capacity), 1 = serial, K>=2 = forced chunk count. Part of
    # the frozen plan because a different K is a different traced
    # program (jit cache key), like every other layout choice.
    moe_pipeline: int = 0

    # ---------------------------------------------------------------
    @property
    def is_null(self) -> bool:
        return self.mesh is None

    def axis_size(self, name: Optional[str]) -> int:
        if self.mesh is None or name is None:
            return 1
        return self.mesh.shape[name]

    @property
    def dp(self) -> Tuple[str, ...] | None:
        return self.dp_axes if self.dp_axes else None

    # -- PartitionSpec builders ---------------------------------------
    def pspec(self, *axes) -> P:
        """Build a PartitionSpec; entries are axis names, tuples or None."""
        return P(*axes)

    def constrain(self, x: jax.Array, spec: P) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def sharding(self, spec: P) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, spec)

    # -- common activation specs --------------------------------------
    def act_btd(self) -> P:
        """(B, S, d_model) residual-stream activations."""
        if self.seq_shard_acts and self.attn_tp_axis:
            return P(self.dp, self.attn_tp_axis, None)
        return P(self.dp, None, None)

    def act_bthd(self, heads_sharded: bool) -> P:
        """(B, S, H, hd) projections."""
        if heads_sharded and self.attn_tp_axis:
            return P(self.dp, None, self.attn_tp_axis, None)
        return P(self.dp, None, None, None)

    def kv_cache_spec(self) -> P:
        """(L, B, S, K, hd) decode KV cache."""
        if self.kv_shard == "heads" and self.attn_tp_axis:
            return P(None, self.dp, None, self.attn_tp_axis, None)
        if self.kv_shard == "seq" and self.attn_tp_axis:
            return P(None, self.dp, self.attn_tp_axis, None, None)
        if self.kv_shard == "seq_all":
            # batch-1 long-context: sequence sharded over every mesh axis
            axes = tuple(self.mesh.axis_names) if self.mesh else ()
            return P(None, None, axes or None, None, None)
        return P(None, self.dp, None, None, None)

    def cache_spec_bshd(self) -> P:
        """(B, S, K, hd) per-layer cache view inside the layer scan."""
        full = self.kv_cache_spec()
        return P(*tuple(full)[1:])

    def ssm_cache_spec(self) -> P:
        """(L, B, d_inner, N) mamba state cache."""
        ax = self.ffn_tp_axis or self.attn_tp_axis
        return P(None, self.dp, ax, None)

    def conv_cache_spec(self) -> P:
        """(L, B, conv_w, d_inner)."""
        ax = self.ffn_tp_axis or self.attn_tp_axis
        return P(None, self.dp, None, ax)

    def act_btdi(self) -> P:
        """(B, S, d_inner) mamba activations: channels on the TP axis."""
        ax = self.ffn_tp_axis or self.attn_tp_axis
        return P(self.dp, None, ax)

    # -- kernel-seam axis resolution (shard_map'ed Pallas dispatch) ----
    def attn_kernel_axes(self, num_q_heads: int,
                         num_kv_heads: int) -> Optional[KernelShardAxes]:
        """shard_map axes for a heads-sharded attention kernel, or None
        when the plan cannot run it per-shard — replicated attention, or
        a head count that does not divide the TP axis (those keep the
        jnp reference path under the same seam)."""
        if (self.is_null or self.attn_mode != "tp_heads"
                or self.attn_tp_axis is None):
            return None
        tp = self.axis_size(self.attn_tp_axis)
        if num_q_heads % tp or num_kv_heads % tp:
            return None
        return KernelShardAxes(self.mesh, self.attn_tp_axis)

    def decode_kernel_axes(self, num_q_heads: int,
                           num_kv_heads: int) -> Optional[KernelShardAxes]:
        """``attn_kernel_axes`` for the cache-appending decode step: the
        KV cache itself must be heads-sharded too, so each device walks
        its own head shard of the page pool (a seq-/seq_all-sharded cache
        would have to be regathered per step)."""
        if self.kv_shard != "heads":
            return None
        return self.attn_kernel_axes(num_q_heads, num_kv_heads)

    def expert_kernel_axes(self, d_ff: int) -> Optional[KernelShardAxes]:
        """shard_map axes for the TP grouped-expert matmuls (d_ff on the
        ffn TP axis), or None when d_ff does not divide (or the experts
        run EP, whose all_to_all shard_map already owns the mesh)."""
        if self.is_null or self.ffn_mode != "tp" or self.ffn_tp_axis is None:
            return None
        if d_ff % self.axis_size(self.ffn_tp_axis):
            return None
        return KernelShardAxes(self.mesh, self.ffn_tp_axis)


NULL_PLAN = ShardingPlan()


def quantized_pspec(spec: P) -> P:
    """Dense weight PartitionSpec -> resident-INT4 packed-layout spec.

    A ``QuantizedExpert`` splits the dense last dim into (n_groups,
    gs//2): sharding of the last dim moves to the group axis (group
    spans tile last-dim spans), the nibble axis is never sharded, and
    the scales/zeros leaves — same rank, trailing dim 1 — take the same
    spec by pytree-prefix broadcast.
    """
    return P(*tuple(spec), None)


def _resolve_plan(mesh: Optional[Mesh], cfg, *, want_attn_tp: bool,
                  want_ep: bool, attn_override: str = "",
                  expert_mode: str = "", kv_shard: str = "") -> ShardingPlan:
    """Shared mode-resolution core (DESIGN.md §5).

    Given the *intent* (attention wants its heads on the TP axis / experts
    want the EP layout), legality-check it against the mesh's model-axis
    size and fall back to the replicated / TP modes when the dimensions
    don't divide. Both the baseline ``make_plan`` and the HAP bridge
    ``HAPPlan.to_sharding_plan`` funnel through here so the mapping rules
    live in exactly one place.
    """
    if mesh is None:
        return NULL_PLAN
    axis_names = mesh.axis_names
    model_ax = "model" if "model" in axis_names else axis_names[-1]
    dp_axes = tuple(a for a in axis_names if a != model_ax)
    tp = mesh.shape[model_ax]

    # attention mode legality
    heads_ok = cfg.has_attention and cfg.num_heads % tp == 0
    attn_mode = attn_override or (
        "tp_heads" if (want_attn_tp and heads_ok) else "replicated")
    if attn_mode == "tp_heads" and not heads_ok:
        attn_mode = "replicated"

    # decode KV cache layout
    if not kv_shard:
        if attn_mode == "tp_heads" and cfg.num_kv_heads % tp == 0:
            kv_shard = "heads"
        else:
            kv_shard = "seq"

    # expert / ffn mode
    ep_ok = cfg.is_moe and cfg.n_routed_experts % tp == 0
    if not expert_mode:
        expert_mode = "ep" if (want_ep and ep_ok) else "tp"
    if expert_mode == "ep" and not ep_ok:
        expert_mode = "tp"

    return ShardingPlan(
        mesh=mesh,
        dp_axes=dp_axes,
        attn_mode=attn_mode,
        attn_tp_axis=model_ax,
        kv_shard=kv_shard,
        ffn_mode=expert_mode,
        ffn_tp_axis=model_ax,
        ep_axis=model_ax if expert_mode == "ep" else None,
    )


def strategy_sharding_plan(mesh: Optional[Mesh], cfg, attn,
                           expert) -> ShardingPlan:
    """Map HAP strategy degrees onto mesh axes (the planner→mesh bridge).

    ``attn`` is an ``AttnStrategy`` (A_d, A_t) and ``expert`` an
    ``ExpertStrategy`` (E_t, E_e) from ``repro.core.strategy``. On a fixed
    mesh a degree becomes an *axis assignment*: attention-TP puts heads on
    the model axis (``tp_heads``) while attention-DP leaves the attention
    weights replicated and the model axis parallelizes only the FFN side;
    expert-EP puts the expert dimension on the model axis, expert-TP the
    expert d_ff. Callers should reach this through
    ``HAPPlan.to_sharding_plan`` rather than directly.
    """
    return _resolve_plan(mesh, cfg,
                         want_attn_tp=attn.tp > 1,
                         want_ep=expert.ep > 1)


def make_plan(mesh: Optional[Mesh], cfg, *, attn_override: str = "",
              expert_mode: str = "", kv_shard: str = "") -> ShardingPlan:
    """Default (baseline) plan for a config on a mesh — internal helper.

    Thin wrapper over ``_resolve_plan`` preferring TP-heads attention and
    EP experts wherever legal. Planner output should go through
    ``HAPPlan.to_sharding_plan`` instead; this remains for static-baseline
    exploration (dry-run overrides) and legacy tests.
    """
    return _resolve_plan(mesh, cfg, want_attn_tp=True, want_ep=True,
                         attn_override=attn_override,
                         expert_mode=expert_mode, kv_shard=kv_shard)


def adapt_plan_for_batch(plan: ShardingPlan, cfg, batch: int,
                         kind: str) -> ShardingPlan:
    """Shape-aware fixups: a batch that doesn't divide the DP axes cannot
    be data-sharded (long_500k has batch 1) — drop DP and spread the KV
    cache sequence over every axis instead."""
    if plan.is_null:
        return plan
    plan = dataclasses.replace(
        plan, seq_shard_acts=(kind in ("train", "prefill")))
    dp_size = 1
    for a in plan.dp_axes:
        dp_size *= plan.axis_size(a)
    if batch % max(dp_size, 1) == 0:
        return plan
    kv = "seq_all" if (kind == "decode" and cfg.has_attention) else plan.kv_shard
    return dataclasses.replace(plan, dp_axes=(), kv_shard=kv)
