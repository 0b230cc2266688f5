"""Jitted dispatch over the Pallas kernels — the kernel-backend seam.

Every hot spot with a custom kernel is reached through one of these
wrappers, selected by a ``KernelBackend``:

- ``ref``    — the pure-jnp oracles in ``repro.kernels.ref`` (XLA fuses
  them well; the correctness ground truth, and the sane default off-TPU),
- ``pallas`` — the Pallas TPU kernels, compiled on real TPU hardware and
  run in interpret mode (kernel body executed as traced jnp, purely for
  validation) everywhere else.

Selection precedence: an explicit ``backend=`` argument, then the
``REPRO_KERNEL_BACKEND`` environment toggle (how the CI
``kernels-interpret`` leg forces the Pallas paths through the whole
suite), then ``default_backend()`` — per-platform: TPU compiles the
kernels, GPU/CPU serve the references. See DESIGN.md §Kernel backends
for the dispatch table and how to add a backend.

**Sharded plans** execute the Pallas kernels too: a ``KernelShardAxes``
(``repro.sharding.specs`` — the plan resolves which mesh axis the
kernel-sharded dim lives on) makes the dispatch wrap the kernel in a
``shard_map`` with that axis on the sharded dimension and everything
else replicated, so each device runs the fused kernel on its own head /
d_ff shard. Attention over heads needs no collective; the row-parallel
grouped matmul psums its partial products. Plans whose dimensions don't
divide the axis (``repeat_kv`` head replication, seq-sharded caches)
keep the jnp reference math under the same seam.

``decode_attention`` is the decode hot path's single entry point: one
cache-appending attention step for BOTH cache layouts — contiguous
``(B, Smax, Hkv, hd)`` rows, or paged ``(num_blocks, block_size, Hkv,
hd)`` pages walked through per-row block tables. A contiguous cache is
dispatched to the paged Pallas kernel as a one-page-per-row pool behind
an identity block table, so both layouts share one kernel.

``DISPATCH_COUNTS`` tallies which branch each trace took (keys like
``decode.pallas_shard_map``); counts tick at trace time, so tests can
assert a given plan actually routed to the kernel, not the fallback.
"""

from __future__ import annotations

import collections
import dataclasses
import enum
import functools
import os
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding.specs import KernelShardAxes

from . import ref
from .flash_attention import flash_attention as _flash_pallas
from .grouped_matmul import grouped_matmul as _gmm_pallas
from .int4_dequant import int4_dequant as _dequant_pallas
from .paged_attention import (
    paged_attention as _paged_pallas,
    prefix_paged_attention as _prefix_pallas,
)


class KernelBackend(str, enum.Enum):
    """Which implementation a kernel dispatch executes."""

    REF = "ref"
    PALLAS = "pallas"


BACKEND_ENV = "REPRO_KERNEL_BACKEND"

# per-platform defaults: the Pallas kernels are TPU-targeted (interpret
# mode is a validation device, not a performance path), so GPU and CPU
# serve the jnp references, which XLA fuses natively on both
_PLATFORM_DEFAULTS = {
    "tpu": KernelBackend.PALLAS,
    "gpu": KernelBackend.REF,
    "cpu": KernelBackend.REF,
}

# trace-time dispatch probe: which branch each op selected. jit caches
# mean a count of N says "traced N times", not "ran N steps" — enough
# for tests to assert a sharded plan actually hit the Pallas path.
DISPATCH_COUNTS: collections.Counter = collections.Counter()


def _record(branch: str) -> None:
    DISPATCH_COUNTS[branch] += 1


def reset_dispatch_counts() -> None:
    DISPATCH_COUNTS.clear()


def default_backend() -> KernelBackend:
    """The sane backend for the current ``jax.default_backend()``."""
    return _PLATFORM_DEFAULTS.get(jax.default_backend(), KernelBackend.REF)


def resolve_backend(backend: Union[KernelBackend, str, None] = None) -> KernelBackend:
    """Normalize a backend spec: None/"auto" -> env toggle -> platform."""
    if backend is None or backend == "auto":
        backend = os.environ.get(BACKEND_ENV) or default_backend()
    return KernelBackend(backend)


def interpret_mode() -> bool:
    """Pallas interpret mode everywhere but on a real TPU backend — the
    one place that decides it; the kernels take no default."""
    return jax.default_backend() != "tpu"


def attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    backend: Union[KernelBackend, str, None] = None,
) -> jax.Array:
    """(B, Hq, Sq, hd) x (B, Hkv, Sk, hd)^2 -> (B, Hq, Sq, hd)."""
    if resolve_backend(backend) is KernelBackend.PALLAS:
        return _flash_pallas(
            q,
            k,
            v,
            causal=causal,
            window=window,
            softcap=softcap,
            scale=scale,
            interpret=interpret_mode(),
        )
    return ref.flash_attention_ref(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
    )


def flash_attention(
    q,
    k,
    v,
    *,
    is_global=True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    shard_axes: Optional[KernelShardAxes] = None,
    backend: Union[KernelBackend, str, None] = None,
) -> jax.Array:
    """Causal full-sequence (prefill) attention in MODEL layout.

    q: (B, S, Hq, hd); k/v: (B, S, Hkv, hd) -> (B, S, Hq, hd). Unlike
    ``attention`` this takes the per-layer traced ``is_global`` flag
    (sliding-window models scan it with the layer stack): ``window > 0``
    applies only when the flag is False, selected by ``lax.cond`` so the
    Pallas kernel keeps its static window argument.

    ``shard_axes`` (a heads-sharded plan's ``attn_kernel_axes``) wraps
    the kernel in a shard_map with q/k/v heads on the plan's TP axis —
    attention is head-parallel, so no collective is needed. The ``ref``
    path serves ``ref.decode_attend_ref`` on the global arrays (XLA
    partitions it under the plan's constraints).
    """
    B, S, Hq, hd = q.shape
    be = resolve_backend(backend)
    if be is not KernelBackend.PALLAS:
        _record("flash.ref")
        pos = jnp.arange(S, dtype=jnp.int32)
        return ref.decode_attend_ref(
            q,
            k,
            v,
            pos,
            pos,
            scale=hd**-0.5 if scale is None else scale,
            softcap=softcap,
            window=window,
            is_global=is_global,
        )

    def one_call(lq, lk, lv, win: int) -> jax.Array:
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (lq, lk, lv))
        out = _flash_pallas(
            qt,
            kt,
            vt,
            causal=True,
            window=win,
            softcap=softcap,
            scale=scale,
            interpret=interpret_mode(),
        )
        return out.transpose(0, 2, 1, 3)

    def local_call(lq, lk, lv, flag) -> jax.Array:
        if window <= 0:
            return one_call(lq, lk, lv, 0)
        return jax.lax.cond(
            jnp.asarray(flag, bool),
            lambda: one_call(lq, lk, lv, 0),
            lambda: one_call(lq, lk, lv, window),
        )

    if shard_axes is None:
        _record("flash.pallas")
        return local_call(q, k, v, is_global)
    _record("flash.pallas_shard_map")
    heads = P(None, None, shard_axes.axis, None)
    fn = jax.shard_map(
        local_call,
        mesh=shard_axes.mesh,
        in_specs=(heads, heads, heads, P()),
        out_specs=heads,
        check_vma=False,
    )
    return fn(q, k, v, jnp.asarray(is_global))


def _normalize_pos(pos) -> jax.Array:
    """Coerce ``pos`` to int32 once at the seam: callers mix python ints,
    scalar arrays and (B,) vectors (the Pallas path used to broadcast
    late, dtype included)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim > 1:
        raise ValueError(f"pos must be a scalar or (B,) vector, got {pos.shape}")
    return pos


def decode_attention(
    q,
    k_cache,
    v_cache,
    k_new,
    v_new,
    pos,
    *,
    block_tables=None,
    prefix_groups=None,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    window: int = 0,
    is_global=True,
    trash_block: int = 0,
    repeat_kv: int = 1,
    constrain: Optional[Callable[[jax.Array], jax.Array]] = None,
    sharded: Optional[bool] = None,
    shard_axes: Optional[KernelShardAxes] = None,
    backend: Union[KernelBackend, str, None] = None,
):
    """One cache-appending decode/chunk attention step, either layout.

    q: (B, C, Hq, hd) rope'd queries; k_new/v_new: (B, C, Hkv, hd) the
    chunk's rope'd K/V; ``pos`` a scalar (lockstep) or (B,) vector of
    write positions — any int dtype, normalized to int32 here.
    ``block_tables`` None means a contiguous ``(B, Smax, Hkv, hd)``
    cache; otherwise the caches are shared ``(num_blocks, block_size,
    Hkv, hd)`` pages addressed through the ``(B, max_blocks)`` table.
    Returns ``(out, k_cache, v_cache)``.

    ``prefix_groups`` (paged only) is the prefix-cache grouping from the
    engine: a ``(2, B)`` int32 array — row 0 each row's prefix-group
    representative, row 1 its shared leading block count (DESIGN.md
    §4d). When given, shared table entries are resolved through the
    representative's table so the kernel walks each shared physical
    block once per group (``prefix_paged_attention`` /
    ``ref.prefix_paged_attention_ref``); token-exact vs the unshared
    path by construction.

    Dispatch: the Pallas kernel serves the unsharded cases directly and
    — when ``shard_axes`` resolves (a heads-sharded plan whose q AND kv
    head counts divide the TP axis, ``ShardingPlan.decode_kernel_axes``)
    — sharded plans through a shard_map that walks each device's head
    shard of the page pool. ``repeat_kv`` head replication (the
    non-dividing TP case) and sharded plans without kernel axes keep the
    reference math, which XLA partitions under ``constrain`` — same
    seam, different implementation.
    """
    pos = _normalize_pos(pos)
    C = q.shape[1]
    if block_tables is None and C > 1 and pos.ndim != 0:
        raise ValueError(
            f"contiguous multi-token append is lockstep-only: a C={C} chunk "
            f"needs a scalar pos, got shape {pos.shape}. Per-row chunked "
            "appends (continuous batching) require a paged cache — pass "
            "block_tables, or decode one token at a time."
        )
    if prefix_groups is not None and block_tables is None:
        raise ValueError("prefix_groups requires a paged cache (block_tables)")
    if sharded is None:
        sharded = constrain is not None or shard_axes is not None
    if (
        resolve_backend(backend) is KernelBackend.PALLAS
        and repeat_kv == 1
        and (not sharded or shard_axes is not None)
    ):
        B = q.shape[0]
        posv = jnp.broadcast_to(jnp.atleast_1d(pos), (B,))
        tables = (
            jnp.arange(B, dtype=jnp.int32)[:, None]  # one page per row
            if block_tables is None
            else block_tables
        )
        if shard_axes is None:
            if prefix_groups is not None:
                _record("decode.pallas_prefix")
                return _prefix_pallas(
                    q,
                    k_cache,
                    v_cache,
                    tables,
                    k_new,
                    v_new,
                    posv,
                    prefix_groups[0],
                    prefix_groups[1],
                    is_global,
                    scale=scale,
                    softcap=softcap,
                    window=window,
                    interpret=interpret_mode(),
                )
            _record("decode.pallas")
            return _paged_pallas(
                q,
                k_cache,
                v_cache,
                tables,
                k_new,
                v_new,
                posv,
                is_global,
                scale=scale,
                softcap=softcap,
                window=window,
                interpret=interpret_mode(),
            )
        heads = P(None, None, shard_axes.axis, None)
        if prefix_groups is not None:
            _record("decode.pallas_prefix_shard_map")

            def local_prefix_step(lq, lk, lv, lt, lkn, lvn, lp, lpg, lflag):
                return _prefix_pallas(
                    lq,
                    lk,
                    lv,
                    lt,
                    lkn,
                    lvn,
                    lp,
                    lpg[0],
                    lpg[1],
                    lflag,
                    scale=scale,
                    softcap=softcap,
                    window=window,
                    interpret=interpret_mode(),
                )

            # same layout as the unshared map below; the grouping operand
            # is replicated like the tables and write positions
            fn = jax.shard_map(
                local_prefix_step,
                mesh=shard_axes.mesh,
                in_specs=(
                    heads,
                    heads,
                    heads,
                    P(None, None),
                    heads,
                    heads,
                    P(None),
                    P(None, None),
                    P(),
                ),
                out_specs=(heads, heads, heads),
                check_vma=False,
            )
            return fn(
                q,
                k_cache,
                v_cache,
                tables,
                k_new,
                v_new,
                posv,
                prefix_groups,
                jnp.asarray(is_global),
            )
        _record("decode.pallas_shard_map")

        def local_step(lq, lk, lv, lt, lkn, lvn, lp, lflag):
            return _paged_pallas(
                lq,
                lk,
                lv,
                lt,
                lkn,
                lvn,
                lp,
                lflag,
                scale=scale,
                softcap=softcap,
                window=window,
                interpret=interpret_mode(),
            )

        # pages/caches and projections shard over heads; tables, write
        # positions and the layer flag are replicated. Batch and page
        # dims stay replicated inside the map — attention is fully
        # head-parallel, so no collective is needed and out_specs just
        # reassemble the head shards.
        fn = jax.shard_map(
            local_step,
            mesh=shard_axes.mesh,
            in_specs=(heads, heads, heads, P(None, None), heads, heads, P(None), P()),
            out_specs=(heads, heads, heads),
            check_vma=False,
        )
        return fn(
            q, k_cache, v_cache, tables, k_new, v_new, posv, jnp.asarray(is_global)
        )
    if block_tables is not None:
        if prefix_groups is not None:
            _record("decode.ref_prefix")
            return ref.prefix_paged_attention_ref(
                q,
                k_cache,
                v_cache,
                block_tables,
                k_new,
                v_new,
                pos,
                prefix_groups[0],
                prefix_groups[1],
                is_global,
                scale=scale,
                softcap=softcap,
                window=window,
                trash_block=trash_block,
                repeat_kv=repeat_kv,
                constrain=constrain,
            )
        _record("decode.ref_paged")
        return ref.paged_attention_ref(
            q,
            k_cache,
            v_cache,
            block_tables,
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
    _record("decode.ref_append")
    return ref.append_attention_ref(
        q,
        k_cache,
        v_cache,
        k_new,
        v_new,
        pos,
        is_global,
        scale=scale,
        softcap=softcap,
        window=window,
        constrain=constrain,
    )


@dataclasses.dataclass(frozen=True)
class QuantizedWeight:
    """A per-group INT4 weight for the dequant-aware grouped matmul.

    The packing is ``repro.core.quantization``'s: two nibbles per uint8,
    low nibble first, per-group f32 scale/zero — the exact layout the
    Pallas ``int4_dequant`` kernel consumes. ``shape`` is the unpacked
    (E, d, f) the matmul sees — registered as static pytree aux data so
    the weight can cross jit boundaries as an argument (the arrays trace,
    the shape stays concrete for ``reshape``).
    """

    packed: jax.Array  # (G, gs // 2) uint8
    scales: jax.Array  # (G, 1) float32
    zeros: jax.Array  # (G, 1) float32
    shape: Tuple[int, ...]  # unpacked rhs shape, e.g. (E, d, f)


jax.tree_util.register_pytree_node(
    QuantizedWeight,
    lambda qw: ((qw.packed, qw.scales, qw.zeros), tuple(qw.shape)),
    lambda shape, leaves: QuantizedWeight(*leaves, shape=shape),
)


@dataclasses.dataclass(frozen=True)
class QuantizedExpert:
    """Resident INT4 expert weight — a *structured* quantized pytree.

    Same nibble packing as ``QuantizedWeight``, but the groups tile the
    LAST weight dim and the leading dims stay explicit:

        packed (*lead, n_groups, gs // 2) uint8
        scales (*lead, n_groups, 1) float32
        zeros  (*lead, n_groups, 1) float32

    Crucially there is NO static ``shape`` aux: the unpacked shape is
    derived from the leaves, so the pytree survives every structural
    transform the serving path applies to dense weights — ``lax.scan``
    slicing a stacked (L, ...) leading axis, shard_map handing each
    device its slice, leading-axis gathers for expert replication, and
    per-leaf ``device_put`` resharding.
    """

    packed: jax.Array
    scales: jax.Array
    zeros: jax.Array

    @property
    def group_size(self) -> int:
        return 2 * self.packed.shape[-1]

    @property
    def shape(self) -> Tuple[int, ...]:
        lead = tuple(self.packed.shape[:-2])
        return lead + (self.packed.shape[-2] * self.group_size,)

    @property
    def ndim(self) -> int:
        return self.packed.ndim - 1

    @property
    def nbytes(self) -> int:
        return self.packed.nbytes + self.scales.nbytes + self.zeros.nbytes


jax.tree_util.register_pytree_node(
    QuantizedExpert,
    lambda qe: ((qe.packed, qe.scales, qe.zeros), None),
    lambda _, leaves: QuantizedExpert(*leaves),
)


def quantize_weight(w, group_size: Optional[int] = None) -> QuantizedExpert:
    """Host-quantize a dense weight into a resident ``QuantizedExpert``.

    Groups tile the last dim (size picked by
    ``quantization.pick_group_size`` when not given), so sharded plans
    that split the last dim keep whole groups per shard.
    """
    import numpy as np

    from repro.core.quantization import quantize_int4_lastdim

    qt = quantize_int4_lastdim(np.asarray(w, np.float32), group_size)
    return QuantizedExpert(
        packed=jnp.asarray(qt.packed),
        scales=jnp.asarray(qt.scales),
        zeros=jnp.asarray(qt.zeros),
    )


def _dequant_weight(rhs, be: KernelBackend, out_dtype) -> jax.Array:
    """Materialize a quantized rhs (dense arrays pass through).

    Handles both the flat transition format (``QuantizedWeight``) and
    the structured resident format (``QuantizedExpert``): the structured
    leaves flatten to the (G, gs/2) slab the dequant kernel consumes,
    then reshape to the derived unpacked shape — so the SAME call works
    on a global weight and on a shard_map-local slice of one.
    """
    if isinstance(rhs, QuantizedExpert):
        half = rhs.packed.shape[-1]
        packed = rhs.packed.reshape(-1, half)
        scales = rhs.scales.reshape(-1, 1)
        zeros = rhs.zeros.reshape(-1, 1)
        shape = rhs.shape
    elif isinstance(rhs, QuantizedWeight):
        packed, scales, zeros, shape = rhs.packed, rhs.scales, rhs.zeros, rhs.shape
    else:
        return rhs
    if be is KernelBackend.PALLAS:
        w = _dequant_pallas(
            packed, scales, zeros, out_dtype=out_dtype, interpret=interpret_mode()
        )
    else:
        w = ref.int4_dequant_ref(packed, scales, zeros, out_dtype=out_dtype)
    return w.reshape(shape)


def grouped_matmul(
    lhs,
    rhs,
    *,
    shard_axes: Optional[KernelShardAxes] = None,
    sharded_dim: str = "out",
    backend: Union[KernelBackend, str, None] = None,
) -> jax.Array:
    """(E, C, d) x (E, d, f) -> (E, C, f) — the expert-FFN seam.

    ``rhs`` may be a dense array, a flat ``QuantizedWeight`` (the INT4
    transition wire format) or a structured ``QuantizedExpert`` (the
    resident serving format), dequantized through the backend's dequant
    path per invocation — resident INT4 serves straight from the packed
    nibbles, and under a TP plan the dequant runs INSIDE the shard_map
    on each device's own slice.

    ``shard_axes`` (a TP plan's ``expert_kernel_axes``) runs the Pallas
    kernel per d_ff shard under shard_map, Megatron-style:

    - ``sharded_dim="out"`` — column-parallel: rhs' LAST dim is on the
      axis, the output stays sharded there, no collective (wi_gate/wi_up),
    - ``sharded_dim="in"``  — row-parallel: the CONTRACTION dim is on the
      axis; each shard's partial product is psummed (wo).

    The ``ref`` backend ignores ``shard_axes`` and serves the global
    einsum, which XLA partitions under the plan's constraints — exactly
    the pre-seam math.
    """
    be = resolve_backend(backend)
    out_dtype = lhs.dtype
    if be is not KernelBackend.PALLAS:
        _record("gmm.ref")
        return ref.grouped_matmul_ref(lhs, _dequant_weight(rhs, be, out_dtype))
    if shard_axes is None:
        w = _dequant_weight(rhs, be, out_dtype)
        _record("gmm.pallas")
        return _gmm_pallas(lhs, w, interpret=interpret_mode())
    ax = shard_axes.axis
    n_shards = shard_axes.mesh.shape[ax]
    # Resident-INT4: keep the rhs packed THROUGH the shard_map and fuse
    # the dequant into each device's local kernel call, so only the
    # device's own nibble slice is ever materialized. Column-parallel
    # ("out") shards the group axis of the packed layout (groups tile
    # the last dim, so group spans == last-dim spans); row-parallel
    # ("in") shards the leading contraction dim, which every group
    # leaves intact. Falls back to a global dequant when the group axis
    # doesn't divide the mesh axis.
    fused = isinstance(rhs, QuantizedExpert) and (
        rhs.packed.shape[-2] % n_shards == 0
        if sharded_dim == "out"
        else rhs.packed.shape[1] % n_shards == 0
    )
    if not fused:
        rhs = _dequant_weight(rhs, be, out_dtype)
    _record("gmm.pallas_shard_map_int4" if fused else "gmm.pallas_shard_map")
    if sharded_dim == "out":
        rhs_spec = P(None, None, ax, None) if fused else P(None, None, ax)
        in_specs = (P(None, None, None), rhs_spec)
        out_specs = P(None, None, ax)

        def local(loc_l, loc_r):
            loc_w = _dequant_weight(loc_r, be, out_dtype)
            return _gmm_pallas(loc_l, loc_w, interpret=interpret_mode())

    elif sharded_dim == "in":
        rhs_spec = P(None, ax, None, None) if fused else P(None, ax, None)
        in_specs = (P(None, None, ax), rhs_spec)
        out_specs = P(None, None, None)

        def local(loc_l, loc_r):
            loc_w = _dequant_weight(loc_r, be, out_dtype)
            part = _gmm_pallas(loc_l, loc_w, interpret=interpret_mode())
            return jax.lax.psum(part, ax)

    else:
        raise ValueError(f"sharded_dim must be 'out'|'in', got {sharded_dim!r}")
    fn = jax.shard_map(
        local,
        mesh=shard_axes.mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    return fn(lhs, rhs)


def a2a_ppermute(x: jax.Array, axis: str, *, split: int,
                 concat: int) -> jax.Array:
    """Tiled ``all_to_all`` decomposed into explicit ``ppermute`` hops.

    Must be called inside a shard_map over ``axis``. Bit-identical to
    ``lax.all_to_all(x, axis, split_axis=split, concat_axis=concat,
    tiled=True)``: the split dim is cut into ``n`` blocks, block ``j``
    travels to device ``j``, and received blocks land on the concat dim
    in source-device order. Shift ``r`` moves every device's block for
    peer ``(me + r) % n`` in one ring hop, so the monolithic exchange
    becomes ``n - 1`` independent sends the scheduler can start as soon
    as each slice is ready — the handle the double-buffered EP schedule
    below interleaves with expert compute. Identity on a 1-device axis
    (the null-mesh parity tests rely on this).
    """
    n = int(jax.lax.psum(1, axis))
    if n == 1:
        return x
    if x.shape[split] % n:
        raise ValueError(
            f"split dim {x.shape[split]} not divisible by axis {axis!r} "
            f"size {n}")
    me = jax.lax.axis_index(axis)
    s = x.shape[split] // n
    c = x.shape[concat]
    shape = list(x.shape)
    shape[split] = s
    shape[concat] = c * n
    out = jnp.zeros(shape, x.dtype)
    mine = jax.lax.dynamic_slice_in_dim(x, me * s, s, split)
    out = jax.lax.dynamic_update_slice_in_dim(out, mine, me * c, concat)
    for r in range(1, n):
        send = jax.lax.dynamic_slice_in_dim(x, ((me + r) % n) * s, s, split)
        recv = jax.lax.ppermute(send, axis,
                                [(i, (i + r) % n) for i in range(n)])
        # the block arriving on shift r left device (me - r) % n
        out = jax.lax.dynamic_update_slice_in_dim(
            out, recv, ((me - r) % n) * c, concat)
    return out


def pipelined_ep_ffn(buf: jax.Array, ffn: Callable[[jax.Array], jax.Array],
                     *, ep_axis: str, chunks: int) -> jax.Array:
    """Micro-batch-pipelined EP exchange + expert FFN (the EPS-MoE
    schedule, DESIGN.md §4e). Must be called INSIDE an EP shard_map.

    ``buf`` is this device's (S, C, d) dispatch buffer; ``ffn`` maps an
    exchanged (S/ep, c*ep, d) slab to its expert outputs. The capacity
    dim is split into ``chunks`` slabs, each running the same
    dispatch-a2a -> FFN -> combine-a2a chain as the serial path. The
    exchanges are the ``a2a_ppermute`` decomposition above and the
    schedule is explicitly double-buffered: slab i+1's dispatch hops are
    issued BEFORE slab i's FFN in program order, so while slab i
    occupies the compute units slab i+1 is already in flight on the
    interconnect (and slab i's combine overlaps slab i+1's FFN) — the
    overlap exists by construction instead of relying on XLA's
    latency-hiding scheduler to find it across a monolithic all_to_all.
    Token-exact with the serial path: routing and capacity assignment
    happened *before* the split, the FFN is row-independent, and the
    concat restores the capacity order.
    """
    K = min(max(int(chunks), 1), buf.shape[1])

    if K <= 1:
        _record("moe.ep_serial")
        ex = functools.partial(jax.lax.all_to_all, axis_name=ep_axis,
                               tiled=True)
        return ex(ffn(ex(buf, split_axis=0, concat_axis=1)),
                  split_axis=1, concat_axis=0)
    _record(f"moe.ep_pipeline_k{K}")
    if int(jax.lax.psum(1, ep_axis)) > 1:
        _record("moe.ep_a2a_ppermute")
    # near-equal slabs; capacity need not divide K (first slabs one wider)
    bounds = [(i * buf.shape[1]) // K for i in range(K + 1)]
    slabs = [buf[:, bounds[i]:bounds[i + 1]] for i in range(K)]
    outs = []
    inflight = a2a_ppermute(slabs[0], ep_axis, split=0, concat=1)
    for i in range(K):
        # double-buffer: issue slab i+1's dispatch before slab i's FFN
        upnext = (a2a_ppermute(slabs[i + 1], ep_axis, split=0, concat=1)
                  if i + 1 < K else None)
        outs.append(a2a_ppermute(ffn(inflight), ep_axis, split=1, concat=0))
        inflight = upnext
    return jnp.concatenate(outs, axis=1)


def int4_dequant(
    packed,
    scales,
    zeros,
    *,
    out_dtype=jnp.bfloat16,
    backend: Union[KernelBackend, str, None] = None,
) -> jax.Array:
    """(G, gs/2) uint8 -> (G, gs) out_dtype."""
    if resolve_backend(backend) is KernelBackend.PALLAS:
        return _dequant_pallas(
            packed, scales, zeros, out_dtype=out_dtype, interpret=interpret_mode()
        )
    return ref.int4_dequant_ref(packed, scales, zeros, out_dtype=out_dtype)
