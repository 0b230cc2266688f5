"""HAP-integrated adaptive inference engine.

The engine owns the full request lifecycle and — when bound to a
``HAPSession`` — keeps the plan *adaptive across batches*:

  1. ``FifoScheduler.next_batch()`` drains a bucket-homogeneous batch;
     the engine asks the session for the plan matching that batch's
     workload bucket (batch size, padded prompt length, output budget).
     Cache hits reuse the earlier ILP solve; a bucket change triggers a
     re-plan and — if the expert layouts differ — the Eq.-6 transition
     between batches (direct reshard or INT4 host restore), logged via
     ``repro.serving``.
  2. Prefill runs under the *prefill* expert strategy.
  3. If the active plan switches strategies (``plan.switches``), the
     expert weights are transitioned before decoding via the mechanism
     the Eq.-6 cost picked — the paper's dynamic parallelism transition.
  4. Decode loops under the *decode* expert strategy.

Without a session the engine is static: a fixed ``ShardingPlan`` and an
optional pinned ``HAPPlan``, exactly the paper's baseline serving mode.
On the CPU dev box the mesh is trivial, so "transition" degenerates to a
numerical identity path — which the tests exploit to verify that serving
through the INT4 backup matches direct serving within quantization
tolerance.

Two serving loops share the engine (DESIGN.md §4/§4b):

  ``run()``              — static batching: a batch admitted together
                           decodes in lockstep until every request stops.
  ``serve_continuous()`` — continuous batching: an in-flight decode set
                           with per-request state; queued requests join
                           at decode-step boundaries (``admit``), advance
                           one fused step per iteration (``step``: a
                           prefill chunk and/or a decode token) and free
                           their resources on completion (``retire``).

Continuous KV memory is **paged** for attention-only models (the
default): a shared block pool (``repro.serving.kv_cache``) replaces the
old per-slot worst-case contiguous reservation, admission checks free
blocks, blocks are allocated on demand as decode advances and freed at
retirement. Prompt prefill is **chunked** — a join feeds its padded
prompt in ``prefill_chunk``-token pieces, each fused with a live decode
step, so admission never stalls decode for more than one chunk.
Mamba/hybrid families (no chunked state append yet) fall back to the
contiguous fixed-slot path.

The whole hot path dispatches through the kernel-backend seam
(``repro.kernels.ops``): the ``kernel_backend`` knob ("ref" | "pallas" |
None for auto, also reachable via ``HAPSession.engine`` and ``serve.py
--kernel-backend``) is threaded into every jitted entry — prefill
(flash attention + grouped expert matmuls), decode/chunk/fused
(paged-attention + grouped matmuls) — so the same engine serves the
pure-jnp reference math or the Pallas kernels without recompiling
anything else. Sharded plans run the kernels per shard via shard_map
when the plan's dimensions divide its TP axis, and fall back to the
partitioned reference math when they don't (DESIGN.md §Kernel
backends).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.flops import Workload
from repro.core.hap import HAPPlan, HAPPlanner
from repro.core.session import round_up
from repro.core.transition import TransitionExecutor
from repro.models import (
    decode_step,
    init_cache,
    init_paged_cache,
    merge_cache_rows,
)
from repro.models import prefill as model_prefill
from repro.models.moe import EXPERT_LEAVES
from repro.sharding.specs import NULL_PLAN, ExpertReplication, quantized_pspec
from . import trace
from .faults import FaultInjector
from .kv_cache import TRASH_BLOCK, BlockAllocator, BlockTable, OutOfBlocks, blocks_for
from .prefix_cache import PrefixCache
from .replication import (
    NextLayerPredictor,
    RoutingTracker,
    plan_replication,
    replication_summary,
)
from .sampling import SamplingParams, sample
from .scheduler import ContinuousScheduler, QueuedRequest

log = logging.getLogger("repro.serving")


def _spanned(name: str, attrs=None):
    """Run the method inside the engine's span ``name``; ``attrs(self,
    result, *args)`` gives the span's attributes when it records."""

    def wrap(fn):
        @functools.wraps(fn)
        def method(self, *args, **kw):
            with self.trace.span(name) as sp:
                out = fn(self, *args, **kw)
                if sp and attrs is not None:
                    sp.attrs.update(attrs(self, out, *args))
            return out

        return method

    return wrap


@dataclasses.dataclass
class Request:
    prompt: Sequence[int]
    max_new_tokens: int = 32
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    # wall-clock budget (ms from submission) for the continuous loop: an
    # expired request retires with status "deadline" at the next step
    # boundary instead of occupying a slot forever. None = no deadline.
    deadline_ms: Optional[float] = None


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: List[int]
    prefill_ms: float
    decode_ms: float
    transition_ms: float
    # terminal status: "ok" (EOS / budget), "cancelled" (engine.cancel),
    # "deadline" (deadline_ms expired). Non-ok completions carry whatever
    # tokens were generated before the request was retired.
    status: str = "ok"
    preemptions: int = 0  # times this request was preempted-and-recomputed
    record: Optional[trace.RequestRecord] = None  # its event times (trace.py)


@dataclasses.dataclass
class EngineStats:
    """Engine-level accounting (survives empty runs, unlike completions)."""

    batches: int = 0  # static batches / continuous live-batch
    #                   generations (cache allocations)
    replans: int = 0  # batches whose active plan changed (the
    #                   source ran only on the cache misses)
    plan_switches: int = 0  # plan changes whose strategies differed
    cache_hits: int = 0
    transition_ms_total: float = 0.0
    last_transition_ms: float = 0.0
    joins: int = 0  # continuous: requests admitted mid-stream
    decode_steps: int = 0  # continuous: decode steps executed
    prefill_chunks: int = 0  # continuous: prefill chunks processed
    fused_steps: int = 0  # continuous: chunk+decode fused iterations
    # prefix-cache accounting (DESIGN.md §4d; zeros with the cache off):
    prefix_hit_blocks: int = 0  # KV blocks adopted instead of recomputed
    prefix_hit_tokens: int = 0  # prefill positions skipped via sharing
    cow_copies: int = 0  # shared blocks forked at first write
    raw_block_need: int = 0  # sum of unshared worst-case admission needs
    effective_block_need: int = 0  # sum of post-sharing admission charges
    # resident-INT4 + online replication (DESIGN.md §5b):
    resident_bytes_saved: int = 0  # dense-minus-packed expert residency delta
    routing_steps: int = 0  # decode steps whose router top-k fed the tracker
    replication_rebalances: int = 0  # replica-set changes applied online
    # async INT4 restore (overlap accounting; zeros with it off):
    async_restores: int = 0  # background restores kicked at decision time
    restore_wait_ms: float = 0.0  # residual barrier wait (the exposed cost)
    restore_overlap_ms: float = 0.0  # kick->barrier window hidden by prefill
    # predictive expert prefetch (DESIGN.md §5c; zeros with it off):
    prefetch_predicted: int = 0  # (layer, expert) rows submitted for pull
    prefetch_hits: int = 0  # staged rows consumed at a restore barrier
    prefetch_misses: int = 0  # rows a barrier restored synchronously
    prefetch_bytes: int = 0  # host bytes pulled by background tasks
    prefetch_hidden_ms: float = 0.0  # pull time spent off the critical path
    prefetch_exposed_ms: float = 0.0  # consume-side restore time still paid
    # request lifecycle + robustness (DESIGN.md §4f; zeros when idle):
    preemptions: int = 0  # victims preempted to reclaim KV blocks
    preempted_tokens: int = 0  # generated tokens stashed for replay
    prefix_evictions_on_pressure: int = 0  # cache blocks evicted mid-stream
    cancelled: int = 0  # requests retired via cancel()
    deadline_expired: int = 0  # requests retired past deadline_ms
    planner_fallbacks: int = 0  # solves degraded to the static plan
    # background-failure propagation (silent log.exception no more):
    background_errors: int = 0  # total background failures, all sites
    prefetch_errors: int = 0  # _prefetch_pull rows that failed
    restore_errors: int = 0  # async restores failed or timed out
    replication_search_errors: int = 0  # searched-degree solves that failed


@dataclasses.dataclass
class _Slot:
    """Per-request in-flight decode state (one live batch row)."""

    req: QueuedRequest
    start: int  # padded prompt length = first decode position
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False  # decode-sampled EOS seen
    prefill_ms: float = 0.0
    transition_ms: float = 0.0
    decode_ms: float = 0.0
    # paged-path state (None/empty on the contiguous fallback):
    table: Optional[BlockTable] = None  # this row's KV block table
    pending: List[np.ndarray] = dataclasses.field(default_factory=list)
    filled: int = 0  # prompt tokens appended so far (starts past a
    #                  matched shared prefix — positions jump the cached run)
    mirrored: bool = False  # host table mirror holds this row's blocks
    #                  (False until the first chunk: prefix-group
    #                  membership requires real table entries, and dead
    #                  decode writes must keep landing in the trash block)

    @property
    def prefilling(self) -> bool:
        return bool(self.pending)


@dataclasses.dataclass
class _LiveBatch:
    """The in-flight decode set: per-slot state plus the shared cache.

    ``pos`` is the host-side source of truth for per-row decode depth;
    it is re-pinned into the cache before every step so drained slots
    stay frozen while live rows advance. Under paging, ``tables`` is the
    host-side mirror of every row's block table (trash-block 0 padded)
    and is re-pinned the same way.
    """

    kv_capacity: int  # logical per-row KV length (tokens)
    slots: List[Optional[_Slot]]
    cache: Any = None  # DecodeCache; paged path allocates eagerly
    pos: Optional[np.ndarray] = None  # (nslots,) int32
    next_tok: Optional[np.ndarray] = None  # (nslots,) int32
    allocator: Optional[BlockAllocator] = None  # paged path only
    max_blocks: int = 0  # block-table width
    tables: Optional[np.ndarray] = None  # (nslots, max_blocks) int32
    prefix: Optional[PrefixCache] = None  # prompt-prefix index over this
    #                  generation's pool (engine prefix_cache knob)

    def active(self) -> List[int]:
        """Rows decoding this step: admitted, prefill complete, not done."""
        return [
            i
            for i, s in enumerate(self.slots)
            if s is not None and not s.done and not s.prefilling
        ]

    def prefilling(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is not None and s.prefilling]


class InferenceEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        plan=None,
        session=None,
        hap: Optional[HAPPlanner] = None,
        hap_plan: Optional[HAPPlan] = None,
        max_batch: int = 8,
        use_int4_transition: Optional[bool] = None,
        eos_id: int = -1,
        paged: Optional[bool] = None,
        kv_block_size: int = 16,
        kv_blocks: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        kernel_backend: Optional[str] = None,
        prefix_cache: bool = False,
        resident_int4: bool = False,
        int4_group_size: Optional[int] = None,
        replicate_experts: int = 0,
        rebalance_interval: int = 32,
        routing_ema: float = 0.9,
        moe_pipeline: int = 0,
        async_transitions: bool = True,
        prefetch: bool = False,
        prefetch_top_p: float = 0.5,
        kv_overcommit: Optional[float] = None,
        max_preemptions: int = 3,
        restore_timeout_s: float = 30.0,
        faults: Optional[FaultInjector] = None,
    ):
        self.cfg = cfg
        self.params = params
        self.plan = plan  # static ShardingPlan (mesh layout) or None
        self.session = session  # HAPSession (adaptive mode) or None
        self.hap = hap
        self.hap_plan = hap_plan  # active HAPPlan (pinned, or per-batch)
        self.eos_id = eos_id
        bucket = session.prompt_bucket if session is not None else 64
        self.scheduler = ContinuousScheduler(
            max_batch=max_batch, bucket=bucket, coalesce_buckets=session is not None
        )
        self.use_int4_transition = use_int4_transition
        # paged KV + chunked prefill for serve_continuous (attention-only
        # families; mamba state has no paged layout or chunked append yet)
        can_page = cfg.has_attention and not cfg.has_mamba
        self.paged = can_page if paged is None else paged
        if self.paged and not can_page:
            raise ValueError("paged KV serving requires an attention-only model")
        if kv_block_size < 1:
            raise ValueError("kv_block_size must be positive")
        self.kv_block_size = kv_block_size
        self.kv_blocks = kv_blocks  # pool size override (blocks, sans trash)
        self.prefill_chunk = prefill_chunk  # None => one chunk per bucket
        # prompt-prefix sharing over the paged pool (DESIGN.md §4d):
        # matched prefixes are adopted (refcounted, COW on divergence),
        # their prefill chunks skipped, admission charged the effective
        # post-sharing block need, and the decode kernel walks shared
        # blocks once per prefix group
        if prefix_cache and not self.paged:
            raise ValueError("prefix_cache requires the paged KV path")
        self.prefix_caching = bool(prefix_cache)
        # kernel backend for the serving hot path — prefill flash, decode
        # attention AND the grouped expert matmuls ("ref" | "pallas");
        # None/"auto" resolves per platform at dispatch (repro.kernels.ops)
        self.kernel_backend = kernel_backend
        self.stats = EngineStats()
        # program spans (profiler on) and per-request records (always):
        # DESIGN.md §4g; ``trace.current()`` finds the newest engine's
        self.trace = trace.install(trace.Recorder())
        # False until a batch has executed under hap_plan: a pre-seeded
        # plan (engine_from_hap) must count as the *initial* plan, not as
        # a previous batch's layout to transition away from.
        self._plan_ran = False
        self._tx = TransitionExecutor()
        # EP micro-batch pipeline depth overlaid on every active plan
        # (0 = follow the plan / auto, 1 = force serial, K>=2 = force K)
        self.moe_pipeline = int(moe_pipeline)
        # async INT4 restore: kick the host dequant+upload onto the
        # TransitionExecutor worker at plan-activation time so it overlaps
        # the batch's prefill; transition_expert_layout() is the barrier
        self.async_transitions = bool(async_transitions)
        self._pending_restore: Optional[tuple] = None
        if use_int4_transition and cfg.is_moe:
            self._backup_experts()
        # resident-INT4 expert serving: quantize the expert FFN leaves once
        # and keep the packed pytrees on device between steps (DESIGN.md
        # §5b); dequant fuses into grouped_matmul per invocation
        self.resident_int4 = bool(resident_int4)
        self.int4_group_size = int4_group_size
        if self.resident_int4 and not cfg.is_moe:
            raise ValueError("resident_int4 requires an MoE config")
        # online hot-expert replication: track router frequencies and grant
        # up to `replicate_experts` extra replicas to the hot experts every
        # `rebalance_interval` tracked decode steps
        self.replicate_experts = int(replicate_experts)
        if self.replicate_experts < 0:
            raise ValueError("replicate_experts must be >= 0")
        if self.replicate_experts and not cfg.is_moe:
            raise ValueError("expert replication requires an MoE config")
        self.rebalance_interval = max(int(rebalance_interval), 1)
        # predictive expert prefetch (DESIGN.md §5c): pull the predicted
        # experts' INT4 restore rows on the background worker while the
        # device runs decode steps, so restore barriers only pay for the
        # missed rows. Needs the routing tracker even without replication.
        self.prefetch = bool(prefetch)
        if self.prefetch and not cfg.is_moe:
            raise ValueError("prefetch requires an MoE config")
        self.prefetch_top_p = float(prefetch_top_p)
        self._tracker: Optional[RoutingTracker] = (
            RoutingTracker(cfg.num_layers, cfg.n_routed_experts, ema=routing_ema)
            if self.replicate_experts or self.prefetch
            else None
        )
        self._predictor: Optional[NextLayerPredictor] = (
            NextLayerPredictor(
                cfg.num_layers, cfg.n_routed_experts, top_p=self.prefetch_top_p
            )
            if self.prefetch
            else None
        )
        # staging buffer: (layer*E) row -> {leaf: prefetched host value},
        # filled by the background worker, consumed (never torn — whole
        # leaves only) at the next restore barrier; rows not in the
        # current predicted window are evicted at issue time
        self._prefetch_stage: Dict[int, Dict[str, Any]] = {}
        self._prefetch_live: set = set()
        self._prefetch_lock = threading.Lock()
        # rebalance cadence is steps-since-last-rebalance, not an exact
        # multiple of the absolute tracker step count — call paths that
        # skip a boundary step must not starve rebalancing
        self._last_rebalance_step = 0
        self._last_workload: Optional[Workload] = None
        self._replication: Optional[ExpertReplication] = None
        self._fn_cache: Dict[Any, Any] = {}
        self._live: Optional[_LiveBatch] = None
        # -- request-lifecycle robustness (DESIGN.md §4f) -----------------
        # optimistic admission: fraction of the output budget charged at
        # admission (None/0 = worst-case reservation, the PR-3 default).
        # Overcommitted pools rely on preemption-by-recompute when the
        # optimism loses, so the paged path is required.
        if kv_overcommit is not None and not 0.0 < kv_overcommit <= 1.0:
            raise ValueError("kv_overcommit must be in (0, 1] or None")
        if kv_overcommit is not None and not self.paged:
            raise ValueError("kv_overcommit requires the paged KV path")
        self.kv_overcommit = kv_overcommit
        self.max_preemptions = max(int(max_preemptions), 1)
        # watchdog on the 1-worker restore executor: a background restore
        # that fails or stalls past this joins the barrier as a sync
        # fallback instead of hanging transition_expert_layout
        self.restore_timeout_s = float(restore_timeout_s)
        # deterministic fault injection, threaded through every
        # degradation surface (allocator / restore worker / planner)
        self.faults = faults
        self._tx.faults = faults
        if session is not None and faults is not None:
            session.faults = faults
        # injectable monotonic clock (tests drive deadlines synthetically)
        self.clock = time.monotonic
        # terminal completions (cancelled / expired / zero-budget preempt)
        # buffered here between lifecycle sweeps; drained by retire()
        self._finished: List[Completion] = []
        if self.resident_int4 and self._expert_leaves():
            self._make_experts_resident()

    # -- jit function cache ----------------------------------------------
    def _jit(self, key, build):
        """One jitted wrapper per (kind, plan) — jax.jit's own cache then
        retraces per argument shape, so a previously-seen shape class
        (slot count, chunk length, KV pool size) never recompiles and
        joins/retirements within a live batch keep shapes constant."""
        if key not in self._fn_cache:
            self._fn_cache[key] = build()
        return self._fn_cache[key]

    # Each jitted program is a named function: its XLA module (and so its
    # ops in a device trace) reads ``jit_<name>``.
    def _prefill_fn(self, plan):
        cfg, be = self.cfg, self.kernel_backend

        def prefill(p, b, ml):
            return model_prefill(p, cfg, b, max_len=ml, plan=plan, backend=be)

        return self._jit(
            ("prefill", plan), lambda: jax.jit(prefill, static_argnums=(2,))
        )

    def _decode_fn(self, plan):
        cfg, be = self.cfg, self.kernel_backend
        collect = self._tracker is not None

        def decode(p, t, c):
            return decode_step(
                p, cfg, t, c, plan=plan, backend=be, collect_routing=collect
            )

        return self._jit(("decode", plan), lambda: jax.jit(decode))

    def _chunk_fn(self, plan):
        """Append one B=1 prefill chunk through a row's block table."""
        cfg, be = self.cfg, self.kernel_backend

        def chunk(p, t, row, c):
            return _chunk_append(p, cfg, t, row, c, plan, be)

        return self._jit(("chunk", plan), lambda: jax.jit(chunk))

    def _cow_fn(self):
        """Copy-on-write fork: duplicate pool pages ``src`` into ``dst``
        across every layer, in one device call (prefix-cache divergence —
        DESIGN.md §4d)."""

        def cow(k, v, src, dst):
            return k.at[:, dst].set(k[:, src]), v.at[:, dst].set(v[:, src])

        return self._jit(("cow",), lambda: jax.jit(cow))

    def _fused_fn(self, plan):
        """One fused continuous step: a prefill chunk for the joining row
        followed by a decode step over the full slot set, in a single jit
        call (one entry per plan; shapes retrace internally). Both halves
        hit the same kernel entry point (``ops.decode_attention``) under
        the engine's backend — the chunk append as a paged C>1 step, the
        decode as a C=1 step."""
        cfg, be = self.cfg, self.kernel_backend
        collect = self._tracker is not None

        def fused(p, chunk_tok, row, dec_tok, cache):
            _, cache = _chunk_append(p, cfg, chunk_tok, row, cache, plan, be)
            return decode_step(
                p, cfg, dec_tok, cache, plan=plan, backend=be, collect_routing=collect
            )

        return self._jit(("fused", plan), lambda: jax.jit(fused))

    def _sharding_for(self, phase: str):
        """Execution layout for a phase under the active plan, with the
        live expert-replication overlay (when any) folded in — a replica
        set is part of the plan, so changing it is a plan change."""
        if (
            self.session is not None
            and self.session.mesh is not None
            and self.hap_plan is not None
        ):
            return self._with_pipeline(
                self._with_replication(
                    self.hap_plan.to_sharding_plan(
                        self.session.mesh, self.cfg, phase=phase
                    )
                )
            )
        return self._with_pipeline(self._with_replication(self.plan))

    def _with_replication(self, plan):
        if self._replication is None:
            return plan
        base = plan if plan is not None else NULL_PLAN
        if base.replication == self._replication:
            return base
        return dataclasses.replace(base, replication=self._replication)

    def _with_pipeline(self, plan):
        """Overlay the engine's EP pipeline knob onto a plan. 0 leaves the
        plan's own ``moe_pipeline`` (auto by default); a forced K is part
        of the plan so it keys the jit cache like any layout choice."""
        if not self.moe_pipeline:
            return plan
        base = plan if plan is not None else NULL_PLAN
        if base.moe_pipeline == self.moe_pipeline:
            return base
        return dataclasses.replace(base, moe_pipeline=self.moe_pipeline)

    # -- transition machinery --------------------------------------------
    def _expert_leaves(self) -> Dict[str, Any]:
        moe = self.params["layers"].get("moe")
        if moe is None:
            return {}
        return {k: moe[k] for k in EXPERT_LEAVES}

    def _backup_experts(self) -> None:
        for name, w in self._expert_leaves().items():
            # per-layer backups keep dequant granularity matched to the
            # upload pipeline (Fig. 3: layer-wise async upload)
            self._tx.backup(f"moe/{name}", w)

    def _quantized_shardings(self, sharding_plan) -> Dict[str, Any]:
        """Per-leaf shardings for the packed ``QuantizedExpert`` layout:
        the dense pspec mapped through ``quantized_pspec`` (a sharded
        last dim moves to the group axis), with any axis the packed
        shape cannot divide dropped back to replicated. Empty on a null
        plan."""
        if sharding_plan is None or getattr(sharding_plan, "is_null", True):
            return {}
        from jax.sharding import PartitionSpec as P

        from repro.models.params import param_pspecs

        pspecs = param_pspecs(self.cfg, sharding_plan)["layers"]["moe"]
        moe = self.params["layers"]["moe"]
        out: Dict[str, Any] = {}
        for n in EXPERT_LEAVES:
            spec = quantized_pspec(pspecs[n])
            packed = getattr(moe[n], "packed", None)
            if packed is not None:
                ent = list(tuple(spec)) + [None] * (packed.ndim - len(tuple(spec)))
                for i, ax in enumerate(ent):
                    if ax is not None and packed.shape[i] % sharding_plan.axis_size(ax):
                        ent[i] = None
                spec = P(*ent)
            out[n] = sharding_plan.sharding(spec)
        return out

    def _make_experts_resident(self) -> None:
        """Flip the expert FFN leaves to resident ``QuantizedExpert``
        pytrees — INT4 becomes the *serving* format, not just the Eq.-6
        transition format. The dense weights are quantized once into
        structured host backups (which the transition path re-uploads),
        the packed/scales/zeros leaves replace each dense leaf on
        device, and dequant runs inside ``ops.grouped_matmul`` per
        invocation (fused per shard under TP expert plans)."""
        from repro.core.quantization import pick_group_size

        moe = dict(self.params["layers"]["moe"])
        saved = 0
        for name in EXPERT_LEAVES:
            key = f"moe/{name}"
            gs = pick_group_size(int(moe[name].shape[-1]), self.int4_group_size or 128)
            dense_bytes = moe[name].nbytes
            self._tx.backup_packed(key, moe[name], gs)
            moe[name] = self._tx.restore_packed(key)
            saved += dense_bytes - moe[name].nbytes
        layers = dict(self.params["layers"])
        layers["moe"] = moe
        self.params = dict(self.params, layers=layers)
        shardings = self._quantized_shardings(self._sharding_for("prefill"))
        for name, sh in shardings.items():
            if sh is not None:
                moe[name] = self._tx.reshard(moe[name], sh)
        self.stats.resident_bytes_saved = int(saved)
        log.info(
            "resident INT4 experts: %.2f MiB dense -> packed residency freed",
            saved / 2**20,
        )

    def _relayout_experts(self, mechanism: str, sharding_plan) -> float:
        """Move the expert weights to a new layout; returns ms.

        ``mechanism`` is ``reshard`` (device_put onto the target sharding;
        identity on a null mesh) or ``int4_upload`` (restore the INT4
        per-group host backup — Table I's quantization round-trip).
        """
        if not self.cfg.is_moe or not self._expert_leaves():
            return 0.0
        # a sync relayout supersedes any in-flight background restore —
        # drain it (never install) so leaves can't tear across layouts
        self._drop_pending_restore()
        t0 = time.perf_counter()
        shardings: Dict[str, Any] = {}
        if sharding_plan is not None and not getattr(sharding_plan, "is_null", True):
            from repro.models.params import param_pspecs

            pspecs = param_pspecs(self.cfg, sharding_plan)["layers"]["moe"]
            shardings = {
                n: sharding_plan.sharding(pspecs[n]) for n in EXPERT_LEAVES
            }
        moe = dict(self.params["layers"]["moe"])
        q_shardings = (
            self._quantized_shardings(sharding_plan) if self.resident_int4 else {}
        )
        for name in EXPERT_LEAVES:
            key = f"moe/{name}"
            if self.resident_int4:
                # resident leaves stay packed through every transition:
                # int4_upload re-uploads the structured backup, reshard
                # device_puts the packed pytree — dense weights never
                # materialize on either side of the move
                if mechanism == "int4_upload":
                    moe[name] = self._sync_restore_leaf(
                        name, sharding=q_shardings.get(name)
                    )
                elif q_shardings.get(name) is not None:
                    moe[name] = self._tx.reshard(moe[name], q_shardings[name])
                continue
            if mechanism == "int4_upload":
                if key not in self._tx._backups:
                    self._tx.backup(key, moe[name])
                moe[name] = self._sync_restore_leaf(
                    name, sharding=shardings.get(name), dtype=moe[name].dtype
                )
            elif shardings.get(name) is not None:
                moe[name] = self._tx.reshard(moe[name], shardings[name])
            # else: direct reshard on a null plan — the identity.
        layers = dict(self.params["layers"])
        layers["moe"] = moe
        self.params = dict(self.params, layers=layers)
        return (time.perf_counter() - t0) * 1e3

    def _restore_leaf_with_stage(self, name: str, sharding=None, dtype=None):
        """Restore one expert leaf from its INT4 backup, consuming any
        prefetched rows from the staging buffer; rows the predictor
        missed restore inline. Falls back to the plain full restore when
        per-row slicing is not exact for this leaf (or prefetch is
        off) — bit-identical output either way."""
        key = f"moe/{name}"
        n_rows = self._tx.prefetch_rows_of(key) if self.prefetch else None
        if n_rows is None:
            if self.resident_int4:
                return self._tx.restore_packed(key, sharding=sharding)
            return self._tx.restore(key, sharding=sharding, dtype=dtype)
        staged = self._prefetch_snapshot(name, n_rows)
        if self.resident_int4:
            return self._tx.restore_packed_with_rows(key, staged,
                                                     sharding=sharding)
        return self._tx.restore_with_rows(key, staged, sharding=sharding,
                                          dtype=dtype)

    def _sync_restore_leaf(self, name: str, sharding=None, dtype=None):
        """Barrier-path leaf restore: the time spent here is prefetch's
        *exposed* cost (what the hidden pulls failed to cover)."""
        t0 = time.perf_counter()
        out = self._restore_leaf_with_stage(name, sharding, dtype)
        if self.prefetch:
            self.stats.prefetch_exposed_ms += (time.perf_counter() - t0) * 1e3
        return out

    def _plan_mechanism(self) -> str:
        """INT4 vs reshard for the active plan's phase switch.

        ``use_int4_transition`` is tri-state: None follows the plan's
        Eq.-6 choice; True/False force the mechanism (False preserves the
        legacy exact-weights opt-out — no lossy INT4 round trip)."""
        if self.use_int4_transition is None:
            return (
                "int4_upload" if self.hap_plan.mechanism == "int4_upload" else "reshard"
            )
        return "int4_upload" if self.use_int4_transition else "reshard"

    # -- async INT4 restore (overlap with prefill) -------------------------
    def _drop_pending_restore(self) -> None:
        """Drain an in-flight background restore without installing it.
        A future that failed (or stalls past the watchdog) is recorded
        and abandoned — the caller is about to relayout synchronously
        anyway, so nothing depends on the dropped results."""
        if self._pending_restore is None:
            return
        _, _, futures, _ = self._pending_restore
        self._pending_restore = None
        for f in futures.values():
            try:
                f.result(timeout=self.restore_timeout_s)
            except Exception:
                log.exception("dropped background restore failed")
                self.stats.restore_errors += 1
                self.stats.background_errors += 1

    def _begin_async_restore(self, phase: str = "decode") -> None:
        """Kick the INT4 expert restore for ``phase`` onto the background
        worker, at plan-switch decision time. The host dequant + device
        upload then overlap the batch's prefill; ``transition_expert_layout``
        joins the futures as the completion barrier, so no step ever sees
        half-restored leaves. No-op unless the active plan switches expert
        layouts via the int4_upload mechanism."""
        if not self.async_transitions:
            return
        if self.hap_plan is None or not self.hap_plan.switches:
            return
        if self._plan_mechanism() != "int4_upload":
            return
        if not self.cfg.is_moe or not self._expert_leaves():
            return
        sharding_plan = self._sharding_for(phase)
        if self._pending_restore is not None:
            p_phase, p_plan, _, _ = self._pending_restore
            if p_phase == phase and p_plan == sharding_plan:
                return  # the right restore is already in flight
            self._drop_pending_restore()
        shardings: Dict[str, Any] = {}
        if sharding_plan is not None and not getattr(sharding_plan, "is_null", True):
            from repro.models.params import param_pspecs

            pspecs = param_pspecs(self.cfg, sharding_plan)["layers"]["moe"]
            shardings = {
                n: sharding_plan.sharding(pspecs[n]) for n in EXPERT_LEAVES
            }
        q_shardings = (
            self._quantized_shardings(sharding_plan) if self.resident_int4 else {}
        )
        moe = self.params["layers"]["moe"]
        futures: Dict[str, Any] = {}
        for name in EXPERT_LEAVES:
            key = f"moe/{name}"
            if self.resident_int4:
                futures[name] = self._tx._executor().submit(
                    self._restore_leaf_with_stage, name, q_shardings.get(name)
                )
            else:
                if key not in self._tx._backups:
                    self._tx.backup(key, moe[name])
                futures[name] = self._tx._executor().submit(
                    self._restore_leaf_with_stage,
                    name,
                    shardings.get(name),
                    moe[name].dtype,
                )
        self._pending_restore = (phase, sharding_plan, futures, time.perf_counter())
        self.stats.async_restores += 1

    def _join_async_restore(self, phase: str) -> Optional[float]:
        """Completion barrier for a kicked restore: wait out the futures,
        install every restored leaf atomically, and return the *exposed*
        wait ms. Returns None when nothing usable is pending — including
        a restore whose target layout no longer matches (the plan moved
        between kick and join); that one is drained and discarded, and
        the caller falls back to the sync path. Torn weights are
        impossible: nothing lands in ``self.params`` until every future
        has resolved, and stale results never land at all."""
        pending = self._pending_restore
        if pending is None:
            return None
        self._pending_restore = None
        p_phase, p_plan, futures, t_kick = pending
        t0 = time.perf_counter()
        try:
            # watchdog: the 1-worker executor serializes restores, so a
            # wedged or failing worker would otherwise hang the barrier —
            # bound the total join and fail over to the sync relayout
            deadline = t0 + self.restore_timeout_s
            results = {
                n: f.result(timeout=max(deadline - time.perf_counter(), 0.0))
                for n, f in futures.items()
            }
        except Exception:
            log.exception(
                "async restore failed/timed out at the barrier; "
                "falling back to the sync relayout"
            )
            self.stats.restore_errors += 1
            self.stats.background_errors += 1
            return None
        wait_ms = (time.perf_counter() - t0) * 1e3
        if p_phase != phase or p_plan != self._sharding_for(phase):
            log.info("async restore discarded: target layout changed in flight")
            return None
        moe = dict(self.params["layers"]["moe"])
        moe.update(results)
        layers = dict(self.params["layers"])
        layers["moe"] = moe
        self.params = dict(self.params, layers=layers)
        self.stats.restore_wait_ms += wait_ms
        self.stats.restore_overlap_ms += (t0 - t_kick) * 1e3
        return wait_ms

    def transition_expert_layout(self) -> float:
        """Execute the prefill->decode expert-layout switch; returns ms.

        When an async restore is in flight for the decode layout this is
        its completion barrier — the returned ms is only the residual
        wait, the rest having overlapped prefill. Otherwise (or when the
        pending restore went stale) the switch runs synchronously."""
        if self.hap_plan is None or not self.hap_plan.switches:
            return 0.0
        ms = self._join_async_restore("decode")
        if ms is not None:
            return ms
        return self._relayout_experts(
            self._plan_mechanism(), self._sharding_for("decode")
        )

    def _restore_prefill_layout(self) -> float:
        """Undo the previous batch's prefill->decode switch so a reused
        switching plan prefills under its *prefill* layout again (the
        reverse Eq.-6 move at the batch boundary); returns ms."""
        if self.hap_plan is None or not self.hap_plan.switches:
            return 0.0
        return self._relayout_experts(
            self._plan_mechanism(), self._sharding_for("prefill")
        )

    # -- online hot-expert replication ------------------------------------
    def _ep_size(self) -> int:
        """EP axis extent of the decode layout (replica totals must pad
        to a multiple of it so the slot axis still shards)."""
        plan = self._sharding_for("decode")
        if plan is None or getattr(plan, "is_null", True):
            return 1
        if plan.ffn_mode != "ep" or plan.ep_axis is None:
            return 1
        return plan.axis_size(plan.ep_axis)

    def _observe_routing(self, cache):
        """Feed a decode step's router top-k block into the frequency
        tracker and strip it from the cache (host-side consumption
        only — it must not ride into the next step's input pytree).
        With prefetch on, this is also where predicted-next-layer pulls
        are issued: the decode step that produced this cache is still
        executing on device (async dispatch), so the background pulls
        run exactly in the window its slab FFNs occupy."""
        if self._tracker is None or getattr(cache, "route_topk", None) is None:
            return cache
        self._tracker.update(np.asarray(cache.route_topk))
        self.stats.routing_steps += 1
        self._maybe_prefetch()
        return cache._replace(route_topk=None)

    # -- predictive expert prefetch (DESIGN.md §5c) -----------------------
    def _prefetch_backup_key(self) -> Optional[str]:
        """The backup leaf prefetch slices, when per-row restore is
        exact for every expert leaf (row spans must land on INT4 group
        boundaries); None disables prefetch for this engine."""
        keys = [f"moe/{n}" for n in EXPERT_LEAVES]
        if any(self._tx.prefetch_rows_of(k) is None for k in keys):
            return None
        return keys[0]

    def _maybe_prefetch(self) -> None:
        """Issue background pulls for the predicted experts' restore
        rows. Runs on the engine thread right after a decode step was
        dispatched; the pulls (host dequant of dense INT4 backups, or
        packed-leaf slices under residency) execute on the
        TransitionExecutor worker while the device computes — the same
        single worker the async restore uses, so pulls and restores
        stay ordered and a consume barrier sees every pull queued
        before it. Mispredicted / unpredicted rows simply stay
        unstaged: the barrier restores them synchronously, token-exact
        by construction (the stage only ever holds bit-exact copies of
        backup rows)."""
        if self._predictor is None or self._tracker is None:
            return
        if self._prefetch_backup_key() is None:
            return
        self._predictor.observe(self._tracker)
        pred = self._predictor.predict()
        E = self.cfg.n_routed_experts
        rows = {
            layer * E + e
            for layer, experts in enumerate(pred)
            for e in experts
        }
        n_rows = self._tx.prefetch_rows_of(f"moe/{EXPERT_LEAVES[0]}")
        rows = {r for r in rows if r < n_rows}
        with self._prefetch_lock:
            # bounded window: evict stale rows the predictor dropped
            for r in [r for r in self._prefetch_stage if r not in rows]:
                del self._prefetch_stage[r]
            fresh = sorted(
                rows - set(self._prefetch_stage) - self._prefetch_live
            )
            self._prefetch_live.update(fresh)
        if not fresh:
            return
        self.stats.prefetch_predicted += len(fresh)
        self._tx._executor().submit(self._prefetch_pull, tuple(fresh))

    def _prefetch_pull(self, rows) -> None:
        """Background worker task: restore each predicted row's leaves
        into the staging buffer. Rows land atomically (all three leaves
        or nothing), so a consume snapshot can never tear an expert."""
        for row in rows:
            t0 = time.perf_counter()
            try:
                staged = {
                    name: self._tx.prefetch_row(f"moe/{name}", row)
                    for name in EXPERT_LEAVES
                }
            except Exception:
                log.exception("prefetch pull failed for row %d", row)
                self.stats.prefetch_errors += 1
                self.stats.background_errors += 1
                with self._prefetch_lock:
                    self._prefetch_live.discard(row)
                continue
            ms = (time.perf_counter() - t0) * 1e3
            nbytes = sum(
                sum(a.nbytes for a in v) if isinstance(v, tuple) else v.nbytes
                for v in staged.values()
            )
            with self._prefetch_lock:
                if row in self._prefetch_live:
                    self._prefetch_live.discard(row)
                    self._prefetch_stage[row] = staged
                    self.stats.prefetch_hidden_ms += ms
                    self.stats.prefetch_bytes += int(nbytes)

    def _prefetch_snapshot(self, name: str, n_rows: int) -> Dict[int, Any]:
        """Staged host values for one leaf + hit/miss accounting for a
        consume barrier. Counted once per restore (on the first leaf) so
        hits/misses tally (layer, expert) rows, not row x leaf."""
        with self._prefetch_lock:
            snap = {r: v[name] for r, v in self._prefetch_stage.items()}
        if name == EXPERT_LEAVES[0]:
            self.stats.prefetch_hits += len(snap)
            self.stats.prefetch_misses += n_rows - len(snap)
        return snap

    def _maybe_rebalance(self) -> bool:
        """Every ``rebalance_interval`` tracked steps SINCE THE LAST
        rebalance, re-plan the replica set from the live routing
        frequencies. (Steps-since, not ``steps % interval`` — a call
        path that skips the exact boundary step, e.g. interleaved
        prefill chunks advancing untracked steps, must fire on its next
        check instead of starving until the next exact multiple.) A
        changed set is a changed ``ShardingPlan`` (fresh jit entries)
        and the weights move through the same Eq.-6 relayout path as
        any plan switch — replication has no bespoke side channel.
        Returns True when a rebalance was applied (callers re-fetch
        their decode fn)."""
        if self._tracker is None or self._tracker.steps == 0:
            return False
        if not self.replicate_experts:
            return False
        if self._tracker.steps - self._last_rebalance_step < self.rebalance_interval:
            return False
        self._last_rebalance_step = self._tracker.steps
        new = plan_replication(
            self._tracker,
            self.replicate_experts,
            align=self._ep_size(),
            degrees=self._searched_degrees(),
        )
        if new.is_identity:
            new = None
        if new == self._replication:
            return False
        old = self._replication
        self._replication = new
        ms = self._relayout_experts("reshard", self._sharding_for("decode"))
        self.stats.replication_rebalances += 1
        self.stats.transition_ms_total += ms
        self.stats.last_transition_ms = ms
        log.info(
            "replication rebalance: %s -> %s (%.1f ms, %s)",
            old.degrees if old is not None else "uniform",
            new.degrees if new is not None else "uniform",
            ms,
            replication_summary(new, self._tracker.frequencies())
            if new is not None
            else {},
        )
        return True

    def _searched_degrees(self) -> Optional[tuple]:
        """Planner-searched per-expert replica degrees: the latency
        model trades each grant's bottleneck-load gain against the
        prefetch bandwidth of keeping the slot fresh
        (``HAPPlanner.searched_replication``), demoting
        ``replicate_experts`` from fixed budget to cap. None (fixed
        water-filling fallback) when the session's planner was never
        built — fitting the latency forests costs ~1 min, which a
        rebalance in a fixed-plan engine must not trigger."""
        sess = self.session
        if (
            sess is None
            or sess._planner is None
            or self.hap_plan is None
            or self._last_workload is None
        ):
            return None
        try:
            return sess.planner.searched_replication(
                self._last_workload,
                self.hap_plan.expert_decode,
                self._tracker.frequencies(),
                max_extra=self.replicate_experts,
                window_steps=self.rebalance_interval,
            )
        except Exception:
            log.exception("replication degree search failed; water-filling")
            self.stats.replication_search_errors += 1
            self.stats.background_errors += 1
            return None

    # -- adaptive re-planning --------------------------------------------
    def _activate_plan(self, batch_workload: Workload, phase: str = "prefill") -> float:
        """Fetch/reuse the bucketed plan for this batch; run the Eq.-6
        inter-batch transition when the active plan changes. Returns ms.

        ``phase`` is the layout the caller is about to serve under:
        static batches enter through their *prefill* layout (a reused
        switching plan gets its prefill layout restored); the paged
        continuous path enters straight into the *decode* layout (fused
        chunk+decode steps run there — DESIGN.md §4b), so a reused plan
        whose experts already sit in the decode layout moves nothing.
        """
        with self.trace.span("engine.plan") as sp:
            hits0 = self.session.hits
            ms = self._switch_plan(batch_workload, phase)
            if sp:
                sp.attrs.update(cache_hit=self.session.hits > hits0, transition_ms=ms)
            return ms

    def _switch_plan(self, batch_workload: Workload, phase: str) -> float:
        hits0 = self.session.hits
        fb0 = self.session.fallbacks
        self._last_workload = batch_workload
        new = self.session.plan_for(batch_workload)
        self.stats.cache_hits += self.session.hits - hits0
        self.stats.planner_fallbacks += self.session.fallbacks - fb0
        old = self.hap_plan
        bucket = self.session.bucket_of(batch_workload).describe()
        if old is None or not self._plan_ran:
            self.hap_plan = new
            log.info("initial plan [%s]: %s", bucket, new.describe())
            # decode-phase entry: put a switching plan's experts in the
            # decode layout once, up front
            return self.transition_expert_layout() if phase == "decode" else 0.0
        if new is old:
            # same cached plan — a switching plan left the experts in the
            # decode layout after the previous batch: restore the prefill
            # layout for a prefill-phase entry, keep it for decode-phase.
            return self._restore_prefill_layout() if phase == "prefill" else 0.0
        self.hap_plan = new
        self.stats.replans += 1
        if (new.attn, new.expert_prefill, new.expert_decode) == (
            old.attn,
            old.expert_prefill,
            old.expert_decode,
        ):
            log.info(
                "re-planned [%s]: strategies unchanged (%s)", bucket, new.describe()
            )
            return self._restore_prefill_layout() if phase == "prefill" else 0.0
        mech, predicted = self.session.transition_between(old, new, batch_workload)
        ms = 0.0
        if mech != "none":
            ms = self._relayout_experts(
                mech,
                new.to_sharding_plan(self.session.mesh, self.cfg, phase=phase)
                if self.session.mesh is not None
                else self.plan,
            )
        elif phase == "decode" and new.switches:
            # Eq.-6 judged old-decode -> new-prefill free, but a decode-
            # phase entry must land in new's *decode* layout
            ms = self.transition_expert_layout()
        self.stats.plan_switches += 1
        log.info(
            "plan switch [%s]: %s -> %s via %s (%.1f ms, predicted %.1f ms)",
            bucket,
            old.describe(),
            new.describe(),
            mech,
            ms,
            predicted * 1e3,
        )
        return ms

    # -- serving -----------------------------------------------------------
    def submit(self, req: Request) -> int:
        deadline = (
            None if req.deadline_ms is None
            else self.clock() + req.deadline_ms / 1e3
        )
        uid = self.scheduler.submit(req.prompt, req.max_new_tokens, deadline=deadline)
        self.trace.submitted(uid)
        return uid

    def cancel(self, uid: int) -> bool:
        """Cancel a request by uid — queued or live. The request retires
        with status "cancelled" (and any tokens generated so far) at the
        next lifecycle sweep; False when the uid is unknown/finished."""
        if self.scheduler.cancel(uid):
            return True
        if self._live is not None:
            for s in self._live.slots:
                if s is not None and s.req.uid == uid:
                    s.req.cancelled = True
                    return True
        return False

    def run(self, sampling: Optional[SamplingParams] = None) -> List[Completion]:
        """Drain the queue; returns completions in uid order."""
        sampling = sampling if sampling is not None else SamplingParams()
        out: List[Completion] = []
        while True:
            batch = self.scheduler.next_batch()
            if batch is None:
                break
            out.extend(self._run_batch(batch, sampling))
        return sorted(out, key=lambda c: c.uid)

    def _run_batch(
        self, batch: List[QueuedRequest], sampling: SamplingParams
    ) -> List[Completion]:
        toks, lens = self.scheduler.pad_batch(batch)
        B, S = toks.shape
        max_new = max(r.max_new_tokens for r in batch)
        max_len = S + max_new + 1
        self.stats.batches += 1

        inter_ms = 0.0
        if self.session is not None:
            inter_ms = self._activate_plan(Workload(batch=B, prompt=S, gen=max_new))
        self._plan_ran = True
        # plan decided: kick the decode-layout INT4 restore onto the
        # background worker so it overlaps this batch's prefill
        self._begin_async_restore("decode")
        prefill_fn = self._prefill_fn(self._sharding_for("prefill"))

        t0 = time.perf_counter()
        logits, cache = prefill_fn(self.params, {"tokens": jnp.asarray(toks)}, max_len)
        logits.block_until_ready()
        prefill_ms = (time.perf_counter() - t0) * 1e3
        for r in batch:
            rec = self.trace.get(r.uid)
            if rec is not None:
                rec.joined, rec.first_chunk, rec.status = t0, t0, "live"
                rec.chunks += 1

        transition_ms = inter_ms + self.transition_expert_layout()
        self.stats.transition_ms_total += transition_ms
        self.stats.last_transition_ms = transition_ms
        decode_fn = self._decode_fn(self._sharding_for("decode"))

        key = jax.random.PRNGKey(sampling.seed)
        generated = np.zeros((B, max_new), np.int32)
        t1 = time.perf_counter()
        next_tok = sample(logits, sampling, key)
        done = np.zeros((B,), bool)
        for step in range(max_new):
            generated[:, step] = np.where(done, self.eos_id, np.asarray(next_tok))
            if step == 0:
                for r in batch:
                    self._first_token(r.uid)
            if step == max_new - 1:
                break
            key, sub = jax.random.split(key)
            logits, cache = decode_fn(self.params, next_tok[:, None], cache)
            cache = self._observe_routing(cache)
            if self._maybe_rebalance():
                decode_fn = self._decode_fn(self._sharding_for("decode"))
            next_tok = sample(logits, sampling, sub)
            if self.eos_id >= 0:
                done |= np.asarray(next_tok) == self.eos_id
                if done.all():
                    break
        decode_ms = (time.perf_counter() - t1) * 1e3

        comps = []
        for i, r in enumerate(batch):
            n = min(r.max_new_tokens, max_new)
            toks_out = [
                int(t) for t in generated[i, :n] if t != self.eos_id or self.eos_id < 0
            ]
            comps.append(
                Completion(
                    r.uid, toks_out, prefill_ms, decode_ms, transition_ms,
                    record=self.trace.finished(r.uid, "ok"),
                )
            )
        return comps

    # -- continuous batching: decode-time joins ---------------------------
    def serve_continuous(
        self, sampling: Optional[SamplingParams] = None
    ) -> List[Completion]:
        """Drain the queue with continuous batching; uid-ordered completions.

        Each iteration admits whatever fits (``admit`` — paged: enough
        free KV blocks; contiguous fallback: enough slot capacity), runs
        ONE fused step (``step``: the head joiner's next prefill chunk
        and/or a decode step over the full slot set) and frees finished
        rows (``retire``) — short requests no longer idle behind long
        ones, and a join stalls decode for at most one chunk. Greedy
        outputs match per-request solo runs exactly: every request is
        prefilled at its own prompt bucket and chunk boundaries only
        re-tile the same causal attention (masked positions contribute
        exact zeros), so its numerics are identical to a solo run
        (stochastic sampling draws an independent per-request key chain
        and is not comparable across the two loops). See DESIGN.md §4b
        for the admit/step/retire state machine.
        """
        sampling = sampling if sampling is not None else SamplingParams()
        key = jax.random.PRNGKey(sampling.seed)
        out: List[Completion] = []
        while len(self.scheduler) or self._live is not None:
            # lifecycle sweep first: cancelled/expired requests — queued
            # or live — retire with a terminal status instead of being
            # served (queued) or looping forever (live)
            self._reap_lifecycle()
            out.extend(self.retire())
            if self._live is None:
                if not len(self.scheduler):
                    break
                self._begin_live_batch()
            self.admit(sampling)
            out.extend(self.retire())  # zero-token budgets end here
            key, sub = jax.random.split(key)
            if not self.step(sampling, sub):
                # nothing runnable: the queue head (if any) outgrows this
                # generation's KV capacity — drain and resize.
                self._live = None
                continue
            out.extend(self.retire())
        out.extend(self.retire())  # any last terminal completions
        return sorted(out, key=lambda c: c.uid)

    @_spanned(
        "engine.begin",
        lambda self, _: {
            "width": self._live.kv_capacity,
            "pool_blocks": self._live.allocator.num_blocks - 1 if self.paged else 0,
        },
    )
    def _begin_live_batch(self) -> None:
        """Size a fresh live batch from the current queue.

        Paged: the block-table width covers the largest queued request's
        need and the block pool holds the *sum* of queued needs (capped
        at every slot full-length) — mixed-length requests share one pool
        instead of each slot reserving the worst case. Contiguous
        fallback: per-slot KV capacity is the largest queued need,
        rounded up to the padding bucket so repeat capacities hit the
        same jit cache entry.
        """
        sch = self.scheduler
        queued = sch.queued()
        cap = round_up(max(sch.kv_need(r) for r in queued), sch.bucket)
        nslots = sch.max_batch
        if self.paged:
            bs = self.kv_block_size
            max_blocks = blocks_for(cap, bs)
            needs = [blocks_for(sch.kv_need(r), bs) for r in queued]
            pool = (
                self.kv_blocks
                if self.kv_blocks is not None
                else min(sum(needs), nslots * max_blocks)
            )
            pool = max(pool, max(needs))  # the head must stay admittable
            allocator = BlockAllocator(pool + 1, bs, faults=self.faults)
            self._live = _LiveBatch(
                kv_capacity=max_blocks * bs,
                slots=[None] * nslots,
                pos=np.zeros((nslots,), np.int32),
                next_tok=np.zeros((nslots,), np.int32),
                allocator=allocator,
                prefix=PrefixCache(allocator) if self.prefix_caching else None,
                max_blocks=max_blocks,
                tables=np.full((nslots, max_blocks), TRASH_BLOCK, np.int32),
                cache=init_paged_cache(
                    self.cfg,
                    nslots,
                    pool + 1,
                    bs,
                    max_blocks,
                    dtype=self.params["embed"].dtype,
                    plan=self._sharding_for("decode"),
                ),
            )
            log.info(
                "live batch: %d slots, %d KV blocks x %d tokens (+trash), "
                "tables %d blocks wide",
                nslots,
                pool,
                bs,
                max_blocks,
            )
        else:
            self._live = _LiveBatch(
                kv_capacity=cap,
                slots=[None] * nslots,
                pos=np.zeros((nslots,), np.int32),
                next_tok=np.zeros((nslots,), np.int32),
            )
            log.info("live batch: %d slots, KV capacity %d tokens", nslots, cap)
        self.stats.batches += 1

    @_spanned("engine.admit", lambda self, joined, *_: {"joined": len(joined)})
    def admit(self, sampling: SamplingParams) -> List[int]:
        """Admit queue-head requests into freed slots at a step boundary.

        Strict head-of-line FIFO. Paged: admission checks *free blocks*
        (``next_fit_blocks``) and queues the prompt as prefill chunks —
        the actual compute happens one chunk per ``step``. Contiguous
        fallback: the head must fit the slot KV capacity and is prefilled
        whole, here. Every admission re-buckets the *live* workload
        (live rows x max padded prompt x max output budget) through the
        session, so a plan switch — and its Eq.-6 reshard/INT4-restore
        transition — fires mid-stream when the workload class changes.
        Returns the joined slot indices.
        """
        live = self._live
        joined: List[int] = []
        while True:
            free = [i for i, s in enumerate(live.slots) if s is None]
            if not free:
                break
            if self.paged:
                r = self.scheduler.next_fit_blocks(
                    live.allocator,
                    live.kv_capacity,
                    prefix_cache=live.prefix,
                    overcommit=self.kv_overcommit,
                )
            else:
                r = self.scheduler.next_fit(live.kv_capacity)
            if r is None:
                break
            self._admit_one(free[0], r, sampling)
            joined.append(free[0])
        return joined

    def _replan_on_join(self, phase: str = "prefill") -> float:
        """Re-bucket the live workload through the session at admission
        time (Eq.-6 transitions fire mid-stream); returns transition ms."""
        inter_ms = 0.0
        if self.session is not None:
            rows = [s for s in self._live.slots if s is not None]
            inter_ms = self._activate_plan(
                Workload(
                    batch=len(rows),
                    prompt=max(s.start for s in rows),
                    gen=max(s.req.max_new_tokens for s in rows),
                ),
                phase=phase,
            )
        self._plan_ran = True
        return inter_ms

    @_spanned("engine.join", lambda self, _, i, r, *__: {"uid": r.uid})
    def _admit_one(self, i: int, r: QueuedRequest, sampling: SamplingParams) -> None:
        live = self._live
        slot = _Slot(req=r, start=self.scheduler.padded_len(r))
        live.slots[i] = slot
        self.stats.joins += 1
        rec = self.trace.get(r.uid)
        if rec is not None:
            rec.status = "live"
            if rec.joined is None:
                rec.joined = time.perf_counter()

        if self.paged:
            # reserve the block budget now: worst-case by default
            # (deadlock safety), or the *expected* need under optimistic
            # admission (kv_overcommit) — growth past the reservation
            # then allocates from spare blocks, and an OutOfBlocks there
            # triggers preemption-by-recompute (DESIGN.md §4f). Blocks
            # materialize lazily as chunks land and decode runs.
            charge = (
                self.scheduler.expected_kv_need(r, self.kv_overcommit)
                if self.kv_overcommit
                else self.scheduler.kv_need(r)
            )
            toks, _ = self.scheduler.pad_batch([r])
            skip = 0
            if live.prefix is not None:
                # re-plan against the cache (consistent with the admission
                # check: nothing registers or evicts in between) and adopt
                # the matched run — the table starts with the shared
                # blocks, reserving only the unmatched remainder
                ap = live.prefix.plan_admission(toks[0], charge)
                skip = ap.skip
                slot.table = BlockTable(
                    live.allocator,
                    charge,
                    shared_blocks=ap.adopt,
                    shared_partial=ap.adopt_partial,
                    owner=f"uid={r.uid}",
                )
                self.stats.prefix_hit_blocks += len(ap.adopt)
                self.stats.prefix_hit_tokens += skip
                self.stats.raw_block_need += ap.raw_blocks
                self.stats.effective_block_need += ap.reserve_blocks
            else:
                slot.table = BlockTable(
                    live.allocator, charge, owner=f"uid={r.uid}"
                )
            chunk = self.prefill_chunk or self.scheduler.bucket
            slot.pending = [
                toks[0, o : o + chunk] for o in range(skip, toks.shape[1], chunk)
            ]
            slot.filled = skip
            # the mirror stays all-trash until the first chunk lands
            # (_ensure_blocks): the fused decode half scatters this row's
            # dead writes, and they must hit the trash block — never an
            # adopted shared page
            live.tables[i, :] = TRASH_BLOCK
            live.pos[i] = skip
            live.next_tok[i] = 0
            # decode-phase activation: a switching plan serves fused
            # chunk+decode steps under its decode layout, and a reused
            # plan's experts are already there — no layout round-trip
            # (DESIGN.md §4b)
            first = not self._plan_ran
            slot.transition_ms = self._replan_on_join(phase="decode")
            if self.session is None and first:
                # sessionless engine with a pinned switching plan: enter
                # the decode layout once, at the first admission
                slot.transition_ms += self.transition_expert_layout()
            self.stats.transition_ms_total += slot.transition_ms
            self.stats.last_transition_ms = slot.transition_ms
            log.info(
                "join uid=%d slot=%d start=%d chunks=%d blocks<=%d (queued %d)",
                r.uid,
                i,
                slot.start,
                len(slot.pending),
                slot.table.budget_blocks,
                len(self.scheduler),
            )
            return

        inter_ms = self._replan_on_join()
        self._begin_async_restore("decode")

        # prefill alone at this request's own bucket (B=1: a bounded set
        # of prefill shapes, and numerics identical to a solo run)
        prefill_fn = self._prefill_fn(self._sharding_for("prefill"))
        toks, _ = self.scheduler.pad_batch([r])
        t0 = time.perf_counter()
        logits, sub_cache = prefill_fn(
            self.params, {"tokens": jnp.asarray(toks)}, live.kv_capacity
        )
        logits.block_until_ready()
        slot.prefill_ms = (time.perf_counter() - t0) * 1e3
        if rec is not None:
            rec.chunks += 1
            if rec.first_chunk is None:
                rec.first_chunk = t0

        slot.transition_ms = inter_ms + self.transition_expert_layout()
        self.stats.transition_ms_total += slot.transition_ms
        self.stats.last_transition_ms = slot.transition_ms

        if live.cache is None:
            n = len(live.slots)
            live.cache = init_cache(
                self.cfg,
                n,
                live.kv_capacity,
                dtype=self.params["embed"].dtype,
                plan=self._sharding_for("decode"),
            )
            live.cache = live.cache._replace(pos=jnp.zeros((n,), jnp.int32))
        live.cache = merge_cache_rows(live.cache, sub_cache, [i])

        tok0 = int(
            np.asarray(
                sample(
                    logits,
                    sampling,
                    jax.random.fold_in(jax.random.PRNGKey(sampling.seed), r.uid),
                )
            )[0]
        )
        live.pos[i] = slot.start
        live.next_tok[i] = tok0
        if r.max_new_tokens >= 1:
            slot.tokens.append(tok0)
            self._first_token(r.uid)
        log.info(
            "join uid=%d slot=%d start=%d (queued %d)",
            r.uid,
            i,
            slot.start,
            len(self.scheduler),
        )

    # -- the per-iteration step ------------------------------------------
    def step(self, sampling: SamplingParams, key=None) -> bool:
        """Advance the live batch by one iteration: the FIFO-first
        joiner's next prefill chunk fused with a decode step when live
        rows exist (paged path), else whichever of the two applies.
        Returns False when nothing is runnable (drain-and-resize)."""
        live = self._live
        pending = live.prefilling()
        active = live.active()
        if not pending and not active:
            return False
        with self.trace.span("engine.step") as sp:
            if pending:
                i = min(pending, key=lambda j: live.slots[j].req.uid)
                s = live.slots[i]
                rec = self.trace.get(s.req.uid)
                first = rec is not None and rec.first_chunk is None
                t_start = sp.started() if first else None
                ran = self._prefill_chunk_step(i, active, sampling, key)
                if ran is not None and rec is not None:
                    rec.chunks += 1
                    if first:
                        rec.first_chunk = t_start
            else:
                ran = self.step_decode(sampling, key)
            if sp and ran is not None:
                sp.attrs.update(self._step_attrs(*ran))
        return True

    def _step_attrs(self, kind: str, rows: List[int], chunk: Optional[tuple]):
        """The ``engine.step`` span's attributes: the kind run, rows
        decoding, the chunk's request and real (unpadded) prompt tokens,
        and the cache positions the step's queries attend in all."""
        live = self._live
        ctx = int(sum(live.pos[j] for j in rows))  # pos already advanced: pos+1 before
        out = {"kind": kind, "rows": len(rows), "chunk_uid": None, "chunk_real": 0}
        if chunk is not None:
            r, lo, n = chunk
            pad = self.scheduler.prompt_bucket(r) - len(r.prompt)
            out.update(chunk_uid=r.uid, chunk_real=max(0, lo + n - max(lo, pad)))
            ctx += n * lo + n * (n + 1) // 2
        out["ctx"] = ctx
        if rows and live.tables is not None:
            # the decode kernel walks the pages up to each row's query
            # position (pos - 1 now), out of the table's full width
            bs = self.kv_block_size
            out["kv_pages_live"] = int(sum((live.pos[j] - 1) // bs + 1 for j in rows))
            out["kv_pages_table"] = len(rows) * live.max_blocks
        return out

    def _ensure_blocks(
        self, i: int, n_tokens: int, write_from: Optional[int] = None
    ) -> None:
        """Lazy block allocation: grow row ``i``'s table to cover
        ``n_tokens`` cache rows and refresh the host table mirror.

        ``write_from`` is the first cache position the caller is about to
        write (a prefill chunk's start, or the decode position): any
        shared block overlapping it is forked first — the (src, dst) page
        copies land on device *before* the write, so the cached prefix
        stays immutable (copy-on-write, DESIGN.md §4d)."""
        live = self._live
        s = live.slots[i]
        if s is None or s.table is None:
            return
        dirty = not s.mirrored
        if write_from is not None:
            copies = s.table.ensure_writable(write_from)
            if copies:
                src = jnp.asarray([c[0] for c in copies], jnp.int32)
                dst = jnp.asarray([c[1] for c in copies], jnp.int32)
                k, v = self._cow_fn()(live.cache.k, live.cache.v, src, dst)
                live.cache = live.cache._replace(k=k, v=v)
                self.stats.cow_copies += len(copies)
                dirty = True
        if s.table.capacity_tokens < n_tokens:
            s.table.ensure_tokens(n_tokens)
            dirty = True
        if dirty:
            live.tables[i] = s.table.padded(live.max_blocks)
            s.mirrored = True

    # -- preemption-by-recompute (DESIGN.md §4f) --------------------------
    def _grow_blocks(
        self, i: int, n_tokens: int, write_from: Optional[int] = None
    ) -> bool:
        """``_ensure_blocks`` with the overcommit contract: an
        ``OutOfBlocks`` mid-growth reclaims pool space (prefix-cache
        eviction first, then preempting a victim) and retries. Returns
        False when row ``i`` itself was the only eligible victim and got
        preempted — the caller must skip its step. Raises the actionable
        ``OutOfBlocks`` when nothing can be reclaimed (every candidate at
        the retry cap)."""
        while True:
            try:
                self._ensure_blocks(i, n_tokens, write_from)
                return True
            except OutOfBlocks as e:
                self._reclaim_blocks(i, e)
                if self._live.slots[i] is None:
                    return False  # row i was preempted to cover the pool

    def _reclaim_blocks(self, i: int, err: OutOfBlocks) -> None:
        """Free at least one pool block for row ``i``'s growth: evict a
        cold prefix-cache entry when one exists, else preempt the
        least-progress victim (prefer any row over ``i`` itself, fewest
        generated tokens first, newest uid on ties, rows at the
        ``max_preemptions`` cap ineligible)."""
        live = self._live
        if live.prefix is not None and live.prefix.evict(1) > 0:
            self.stats.prefix_evictions_on_pressure += 1
            return
        victims = [
            (j, s)
            for j, s in enumerate(live.slots)
            if s is not None
            and not s.done
            and s.req.preemptions < self.max_preemptions
        ]
        if not victims:
            raise OutOfBlocks(
                f"wedged: no preemptable victim (every live request is at "
                f"the retry cap of {self.max_preemptions}); "
                f"{live.allocator.describe()}"
            ) from err
        j, _ = min(
            victims, key=lambda t: (t[0] == i, len(t[1].tokens), -t[1].req.uid)
        )
        self._preempt(j)

    def _preempt(self, j: int) -> None:
        """Preempt row ``j``: free its blocks, stash its generated tokens
        and re-enqueue prompt+generated as a fresh prefill at the queue
        head. Token-exact under greedy sampling: the recompute replays
        the identical token row at the identical padding, and rides the
        prefix cache when the prompt was registered. A victim whose
        remaining budget is exhausted completes instead of requeueing."""
        live = self._live
        s = self._free_slot(j)
        r = s.req
        r.preemptions += 1
        self.stats.preemptions += 1
        rec = self.trace.get(r.uid)
        if rec is not None:
            rec.preemptions += 1
            rec.status = "queued"
        self.stats.preempted_tokens += len(s.tokens)
        remaining = r.max_new_tokens - len(s.tokens)
        if s.done or remaining <= 0:
            # defensive: a finished row should have retired already, but
            # if preemption races a retire boundary, complete it here
            toks = list(r.stashed) + [
                t for t in s.tokens if t != self.eos_id or self.eos_id < 0
            ]
            self._finished.append(
                Completion(
                    r.uid, toks, s.prefill_ms, s.decode_ms, s.transition_ms,
                    preemptions=r.preemptions,
                    record=self.trace.finished(r.uid, "ok"),
                )
            )
            log.info("preempt-complete uid=%d slot=%d", r.uid, j)
            return
        r.stashed = list(r.stashed) + list(s.tokens)
        r.max_new_tokens = remaining
        self.scheduler.requeue(r)
        log.info(
            "preempt uid=%d slot=%d (%d tokens stashed, %d budget left, "
            "preemption %d/%d)",
            r.uid, j, len(r.stashed), remaining, r.preemptions,
            self.max_preemptions,
        )

    def _prefix_group_arrays(self) -> np.ndarray:
        """The (2, nslots) prefix-group operand for the decode kernel:
        row 0 maps every slot to its group representative (itself when
        unshared), row 1 holds the leading shared-block count. Rows whose
        first ``n_shared`` physical blocks are identical form one group —
        the kernel walks those pages through the representative's table,
        so a shared prefix is streamed once per group, not once per row.
        Only mirrored slots participate: before its first chunk a row's
        mirror is all-trash and must not anchor (or join) a group."""
        live = self._live
        n = len(live.slots)
        reps = np.arange(n, dtype=np.int32)
        nsh = np.zeros((n,), np.int32)
        first: Dict[tuple, int] = {}
        for i, s in enumerate(live.slots):
            if s is None or s.table is None or not s.mirrored:
                continue
            if s.table.n_shared == 0:
                continue
            key = tuple(s.table.blocks[: s.table.n_shared])
            rep = first.setdefault(key, i)
            if rep != i:
                reps[i] = rep
                nsh[i] = s.table.n_shared
        return np.stack([reps, nsh])

    def _pinned_cache(self):
        """The live cache with host-side pos (and block tables) pinned in,
        so drained slots stay frozen while live rows advance. With the
        prefix cache on, the per-step group map rides along the same way."""
        live = self._live
        cache = live.cache._replace(pos=jnp.asarray(live.pos))
        if self.paged:
            cache = cache._replace(block_tables=jnp.asarray(live.tables))
            if live.prefix is not None:
                cache = cache._replace(
                    prefix_groups=jnp.asarray(self._prefix_group_arrays())
                )
        return cache

    def _prefill_chunk_step(
        self, i: int, active: List[int], sampling: SamplingParams, key
    ) -> Optional[tuple]:
        """Process the joining row's next prompt chunk; fuse it with a
        decode step over the live rows when there are any and the chunk
        is not the last (the final chunk's logits feed sampling, which
        the fused entry does not return).

        Block growth runs through the preemption-aware ``_grow_blocks``
        path: any row — including the joiner itself — may be preempted
        mid-growth to reclaim pool space, so the step re-checks what is
        still live before touching the device.

        Returns ``(kind, decoding rows, (request, chunk start, chunk
        length))`` for the step's span, or None when the joiner was
        preempted instead."""
        live = self._live
        s = live.slots[i]
        chunk = s.pending[0]
        C = len(chunk)
        final = len(s.pending) == 1
        grow = active if not final else []
        with self.trace.span("engine.blocks") as sp:
            pre = self.stats.preemptions
            if self._grow_blocks(i, s.filled + C, write_from=s.filled) and grow:
                active = self._grow_decode_rows(grow)
            if sp:
                sp.attrs.update(
                    rows=1 + len(grow), preemptions=self.stats.preemptions - pre
                )
        if live.slots[i] is None:
            return None  # the joiner was preempted to cover the pool
        s.pending.pop(0)
        plan = self._sharding_for("decode")
        self.stats.prefill_chunks += 1
        this = (s.req, s.filled, C)

        if active and not final:
            fn = self._fused_fn(plan)
            with self.trace.span("engine.inputs"):
                chunk_tok = jnp.asarray(chunk)[None, :]
                dec_tok = jnp.asarray(live.next_tok)[:, None]
                cache = self._pinned_cache()
            with self.trace.span("engine.dispatch") as sp:
                t0 = sp.started()
                logits, live.cache = fn(self.params, chunk_tok, i, dec_tok, cache)
            with self.trace.span("engine.sample"):
                toks = sample(logits, sampling, key)
            with self.trace.span("engine.sync") as sp:
                toks = np.asarray(toks)
            step_ms = (sp.ended() - t0) * 1e3
            with self.trace.span("engine.book"):
                s.filled += C
                live.pos[i] = s.filled
                # the fused step's wall time is booked once, as the active
                # rows' decode step (the chunk rides along for free); the
                # joiner's prefill_ms counts only its unfused chunk steps
                self.stats.decode_steps += 1
                self.stats.fused_steps += 1
                live.cache = self._observe_routing(live.cache)
                self._apply_sampled(toks, active, step_ms)
                self._maybe_rebalance()
            return "fused", active, this

        fn = self._chunk_fn(plan)
        with self.trace.span("engine.inputs"):
            chunk_tok = jnp.asarray(chunk)[None, :]
            cache = self._pinned_cache()
        with self.trace.span("engine.dispatch") as sp:
            t0 = sp.started()
            logits, live.cache = fn(self.params, chunk_tok, i, cache)
        if final:
            with self.trace.span("engine.sample"):
                # same per-request key chain as a solo run's prefill sample
                tok0 = sample(
                    logits,
                    sampling,
                    jax.random.fold_in(jax.random.PRNGKey(sampling.seed), s.req.uid),
                )
        with self.trace.span("engine.sync") as sp:
            if final:
                tok0 = int(np.asarray(tok0)[0])
            else:
                logits.block_until_ready()
        s.prefill_ms += (sp.ended() - t0) * 1e3
        with self.trace.span("engine.book"):
            s.filled += C
            live.pos[i] = s.filled
            if final:
                live.next_tok[i] = tok0
                if s.req.max_new_tokens >= 1:
                    s.tokens.append(tok0)
                    self._first_token(s.req.uid)
                if live.prefix is not None:
                    # index the completed prompt so later admissions can adopt
                    # it; the cache takes its own block references, so the run
                    # outlives this request's retirement until evicted
                    live.prefix.register(
                        self.scheduler.pad_batch([s.req])[0][0], s.table.blocks
                    )
                log.info(
                    "prefill complete uid=%d slot=%d (%d tokens, %d blocks)",
                    s.req.uid,
                    i,
                    s.filled,
                    len(s.table),
                )
        return "chunk", [], this

    def _first_token(self, uid: int) -> None:
        rec = self.trace.get(uid)
        if rec is not None and rec.first_token is None:
            rec.first_token = time.perf_counter()

    def _apply_sampled(
        self, toks: np.ndarray, active: List[int], step_ms: float
    ) -> None:
        live = self._live
        for i in active:
            s = live.slots[i]
            live.pos[i] += 1
            s.decode_ms += step_ms
            t = int(toks[i])
            live.next_tok[i] = t
            if self.eos_id >= 0 and t == self.eos_id:
                s.done = True  # stop; EOS is never emitted
                continue
            s.tokens.append(t)

    def _grow_decode_rows(self, active: List[int]) -> List[int]:
        """Grow each decoding row's table to cover its next write (the
        preemption-aware path); returns the rows still live after."""
        live = self._live
        for j in active:
            if live.slots[j] is not None:
                self._grow_blocks(j, int(live.pos[j]) + 1, write_from=int(live.pos[j]))
        return [j for j in active if live.slots[j] is not None]

    def step_decode(self, sampling: SamplingParams, key=None) -> Optional[tuple]:
        """One decode step over the FULL slot set (freed/done rows are
        frozen host-side): constant decode shapes per (plan, slot count),
        so joins and retirements never trigger a recompile. Returns
        ``("decode", decoding rows, None)`` for the step's span, or None
        when every row was preempted instead."""
        live = self._live
        active = live.active()
        if self.paged:
            with self.trace.span("engine.blocks") as sp:
                pre = self.stats.preemptions
                rows = len(active)
                active = self._grow_decode_rows(active)
                if sp:
                    sp.attrs.update(rows=rows, preemptions=self.stats.preemptions - pre)
            if not active:
                return None  # every decode row was preempted to cover the pool
        decode_fn = self._decode_fn(self._sharding_for("decode"))
        with self.trace.span("engine.inputs"):
            tok = jnp.asarray(live.next_tok)[:, None]
            cache = self._pinned_cache()
        with self.trace.span("engine.dispatch") as sp:
            t0 = sp.started()
            logits, live.cache = decode_fn(self.params, tok, cache)
        with self.trace.span("engine.sample"):
            toks = sample(logits, sampling, key)
        with self.trace.span("engine.sync") as sp:
            toks = np.asarray(toks)
        step_ms = (sp.ended() - t0) * 1e3
        with self.trace.span("engine.book"):
            self.stats.decode_steps += 1
            live.cache = self._observe_routing(live.cache)
            self._apply_sampled(toks, active, step_ms)
            self._maybe_rebalance()
        return "decode", active, None

    def _free_slot(self, i: int) -> "_Slot":
        """Release row ``i``'s resources (blocks back to the pool, mirror
        to trash) and empty the slot; returns the old slot state."""
        live = self._live
        s = live.slots[i]
        if s.table is not None:
            s.table.free()
            live.tables[i, :] = TRASH_BLOCK
        # a drained row still rides every decode step: at position 0 the
        # decode kernel walks one trash page for it, not its old length
        live.pos[i] = 0
        s.pending = []
        live.slots[i] = None
        live.next_tok[i] = 0
        return s

    def _expired(self, r: QueuedRequest) -> bool:
        return r.deadline is not None and self.clock() >= r.deadline

    @_spanned("engine.reap")
    def _reap_lifecycle(self) -> None:
        """Retire cancelled/expired requests — queued or live — with a
        terminal status (the request-lifecycle contract, DESIGN.md §4f).
        Runs at every step boundary; completions land in ``_finished``
        and drain through ``retire()``. Partial output (stashed replay +
        tokens generated so far) is returned, never silently dropped."""
        for r in list(self.scheduler.queued()):
            if not (r.cancelled or self._expired(r)):
                continue
            self.scheduler.remove(r)
            status = "cancelled" if r.cancelled else "deadline"
            self._count_terminal(status)
            self._finished.append(
                Completion(
                    r.uid, list(r.stashed), 0.0, 0.0, 0.0,
                    status=status, preemptions=r.preemptions,
                    record=self.trace.finished(r.uid, status),
                )
            )
            log.info("reap queued uid=%d (%s)", r.uid, status)
        live = self._live
        if live is None:
            return
        for i, s in enumerate(live.slots):
            if s is None or not (s.req.cancelled or self._expired(s.req)):
                continue
            status = "cancelled" if s.req.cancelled else "deadline"
            self._count_terminal(status)
            self._free_slot(i)
            toks = list(s.req.stashed) + [
                t for t in s.tokens if t != self.eos_id or self.eos_id < 0
            ]
            self._finished.append(
                Completion(
                    s.req.uid, toks, s.prefill_ms, s.decode_ms,
                    s.transition_ms, status=status,
                    preemptions=s.req.preemptions,
                    record=self.trace.finished(s.req.uid, status),
                )
            )
            log.info(
                "reap live uid=%d slot=%d (%s, %d tokens)",
                s.req.uid, i, status, len(toks),
            )

    def _count_terminal(self, status: str) -> None:
        if status == "cancelled":
            self.stats.cancelled += 1
        elif status == "deadline":
            self.stats.deadline_expired += 1

    @_spanned("engine.retire", lambda self, comps: {"retired": len(comps)})
    def retire(self) -> List[Completion]:
        """Free slots whose request hit EOS or its output budget; returns
        their completions plus any buffered terminal (cancelled/expired/
        zero-budget) ones. Paged: KV blocks go back to the free pool;
        contiguous: the row is reused by the next join."""
        comps: List[Completion] = list(self._finished)
        self._finished.clear()
        live = self._live
        if live is None:
            return comps
        for i, s in enumerate(live.slots):
            if s is None or not (s.done or len(s.tokens) >= s.req.max_new_tokens):
                continue
            toks = list(s.req.stashed) + [
                t for t in s.tokens if t != self.eos_id or self.eos_id < 0
            ]
            comps.append(
                Completion(
                    s.req.uid, toks, s.prefill_ms, s.decode_ms, s.transition_ms,
                    preemptions=s.req.preemptions,
                    record=self.trace.finished(s.req.uid, "ok"),
                )
            )
            self._free_slot(i)
            log.info("retire uid=%d slot=%d (%d tokens)", s.req.uid, i, len(toks))
        return comps


def _chunk_append(params, cfg: ModelConfig, chunk_tok, row, cache, plan, backend=None):
    """Append a B=1 prompt chunk to paged-cache row ``row`` (traced).

    Slices the row's block-table/pos view out of the live cache, runs the
    multi-token ``decode_step`` append, and splices the updated pages and
    position back. Returns (last-position logits (1, V), cache)."""
    sub = cache._replace(
        block_tables=jax.lax.dynamic_slice_in_dim(cache.block_tables, row, 1, axis=0),
        pos=jax.lax.dynamic_slice_in_dim(cache.pos, row, 1, axis=0),
        # the row's own table already holds any adopted shared blocks, so
        # the B=1 chunk append reads them directly — no group indirection
        prefix_groups=None,
    )
    logits, sub = decode_step(params, cfg, chunk_tok, sub, plan=plan, backend=backend)
    cache = cache._replace(
        k=sub.k,
        v=sub.v,
        pos=jax.lax.dynamic_update_slice(cache.pos, sub.pos, (row,)),
    )
    return logits, cache


def engine_from_hap(
    cfg: ModelConfig,
    params,
    chip: str,
    n_devices: int,
    prompt_len: int,
    gen_len: int,
    batch: int,
    model=None,
    plan=None,
) -> InferenceEngine:
    """Legacy convenience — now a thin wrapper over ``HAPSession.engine``.

    Prefer building a ``HAPSession`` directly: it keeps the planner and
    the bucketed plan cache alive across engine runs.
    """
    from repro.core.flops import Workload
    from repro.core.session import HAPSession

    # prompt_bucket stays at the legacy 64-token padding granularity —
    # per-batch re-planning adapts to the actual prompt lengths anyway.
    session = HAPSession(
        cfg, chip, n_devices, model=model, prompt_bucket=64, gen_bucket=max(gen_len, 1)
    )
    eng = session.engine(params, max_batch=batch)
    eng.plan = plan
    # legacy contract: plan eagerly for the stated workload so hap_plan is
    # readable before the first run (batches still re-plan adaptively).
    eng.hap_plan = session.plan_for(
        Workload(batch=batch, prompt=prompt_len, gen=gen_len)
    )
    return eng
