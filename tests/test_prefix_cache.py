"""Prefix-cache subsystem: COW block sharing + prefix-aware kernels.

Four altitudes (DESIGN.md §4d): the host-level cache index (match /
register / evict, hash-collision safety, refcount lifecycle including
retire-order independence and double-free diagnostics), copy-on-write
forking at and inside block boundaries, effective-need admission when
the pool only fits the shared prefix, kernel parity of the prefix-group
paged-attention path (Pallas interpret vs jnp oracle vs the plain paged
oracle), and the serving engine end-to-end — token-exact greedy outputs
with the cache on vs off on the null mesh for both backends, with the
TP2 mesh variant as a subprocess test.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced
from repro.core import HAPSession
from repro.core.hap import fixed_plan
from repro.kernels import ops, ref
from repro.kernels import paged_attention as pa
from repro.kernels.paged_attention import paged_attention, prefix_paged_attention
from repro.models import init_params
from repro.serving import Request
from repro.serving.kv_cache import (TRASH_BLOCK, BlockAllocator, BlockTable,
                                    DoubleFree)
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import ContinuousScheduler


# ---------------------------------------------------------------------------
# host-level: match / register / refcounts (no model, no devices)
# ---------------------------------------------------------------------------
def _registered_donor(a, tokens, budget=None):
    """Allocate a donor table for ``tokens``, register it, retire it.
    Returns (cache, donor_blocks) — the cache now holds the only refs."""
    pc = PrefixCache(a)
    t = BlockTable(a, budget or len(tokens))
    t.ensure_tokens(len(tokens))
    blocks = list(t.blocks)
    pc.register(np.asarray(tokens, np.int32), blocks)
    t.free()
    return pc, blocks


def test_match_register_roundtrip_full_blocks_and_tail():
    a = BlockAllocator(8, block_size=4)
    toks = np.arange(1, 11, dtype=np.int32)          # 10 tokens: 2 full + tail
    pc, blocks = _registered_donor(a, toks, budget=12)
    assert all(a.refcount(b) == 1 for b in blocks)   # cache refs survive retire

    m = pc.match(toks)                               # identical prompt
    assert m.n_tokens == 10 and m.blocks == blocks
    div = toks.copy(); div[9] = 99                   # diverges at token 9
    m = pc.match(div)                                # partial tail: 8 full + 1
    assert m.n_tokens == 9 and m.blocks == blocks
    div = toks.copy(); div[5] = 99                   # diverges inside block 1
    m = pc.match(div)                                # only block 0 matches;
    assert m.n_tokens == 4 and m.blocks == blocks[:1]  # no tail at offset 4


def test_register_dedup_never_double_refs():
    """Re-registering an identical run (an adopter finishing its prefill)
    must not add a second cache reference — first writer wins."""
    a = BlockAllocator(8, block_size=4)
    toks = np.arange(1, 11, dtype=np.int32)
    pc, blocks = _registered_donor(a, toks, budget=12)
    t2 = BlockTable(a, 12)
    t2.ensure_tokens(12)
    assert pc.register(toks, t2.blocks) == 0          # identical runs: no-op
    assert all(a.refcount(b) == 1 for b in blocks)
    assert all(a.refcount(b) == 1 for b in t2.blocks)


def test_hash_collision_never_shares_blocks():
    """A colliding hash must never alias different token runs: every hit
    is verified by a full token-run compare."""
    a = BlockAllocator(8, block_size=4)
    pc = PrefixCache(a, hash_fn=lambda data: 7)       # everything collides
    t = BlockTable(a, 8)
    t.ensure_tokens(8)
    pc.register(np.arange(1, 9, dtype=np.int32), t.blocks)
    other = np.arange(101, 109, dtype=np.int32)       # same hash, other tokens
    assert pc.match(other).n_tokens == 0
    assert pc.match(other).blocks == []
    m = pc.match(np.arange(1, 9, dtype=np.int32))     # the real run still hits
    assert m.n_tokens == 8 and m.blocks == t.blocks


def test_double_free_raises_actionable_and_table_free_idempotent():
    a = BlockAllocator(4, block_size=4)
    t = BlockTable(a, 8)
    t.ensure_tokens(8)
    b = t.blocks[0]
    t.free()
    t.free()                                          # idempotent: no raise
    with pytest.raises(DoubleFree, match="exactly once per holder"):
        a.free_block(b)                               # direct double release
    with pytest.raises(DoubleFree):
        a.free_block(TRASH_BLOCK)


def test_cow_fork_at_block_boundary_vs_mid_block():
    """Writing at a block boundary never forks the preceding full block;
    writing mid-way into a partially-shared tail forks exactly it."""
    a = BlockAllocator(12, block_size=4)
    toks = np.arange(1, 12, dtype=np.int32)           # 11 tokens
    pc, blocks = _registered_donor(a, toks, budget=16)

    # boundary: adopt the 2 fully-matched blocks, first write at token 8
    t1 = BlockTable(a, 16, shared_blocks=blocks[:2])
    assert t1.ensure_writable(8) == []                # nothing to fork
    assert t1.n_shared == 2 and t1.blocks[:2] == blocks[:2]

    # mid-block: adopt the partial tail too, first write at token 9
    t2 = BlockTable(a, 16, shared_blocks=blocks, shared_partial=True)
    copies = t2.ensure_writable(9)
    assert len(copies) == 1 and copies[0][0] == blocks[2]
    assert t2.n_shared == 2                           # tail left the prefix
    assert t2.blocks[2] != blocks[2]                  # private fork swapped in
    assert a.refcount(blocks[2]) == 1                 # cache keeps the original
    assert t2.ensure_writable(9) == []                # already exclusive
    t1.free(); t2.free()
    assert a.refcount(blocks[0]) == 1                 # back to cache-only


def test_retire_order_independence():
    """Donor-then-adopter and adopter-then-donor retirement must land in
    the same allocator state — refcounts make release order irrelevant."""
    for donor_first in (True, False):
        a = BlockAllocator(12, block_size=4)
        toks = np.arange(1, 9, dtype=np.int32)
        pc = PrefixCache(a)
        donor = BlockTable(a, 12)
        donor.ensure_tokens(8)
        pc.register(toks, donor.blocks)
        adopter = BlockTable(a, 12, shared_blocks=donor.blocks)
        shared = list(donor.blocks)
        assert all(a.refcount(b) == 3 for b in shared)  # donor+cache+adopter
        first, second = (donor, adopter) if donor_first else (adopter, donor)
        first.free()
        assert all(a.refcount(b) == 2 for b in shared)
        second.free()
        assert all(a.refcount(b) == 1 for b in shared)  # cache-only
        assert pc.evict(len(shared)) == len(shared)     # now evictable
        assert a.num_free == 11 and a.num_reserved == 0


def test_admission_when_pool_only_fits_shared_prefix():
    """Effective-need admission: a head whose raw block need exceeds the
    free pool is still admitted when the shared prefix covers the gap."""
    a = BlockAllocator(5, block_size=8)               # 4 allocatable
    toks16 = list(range(1, 17))                       # bucket 8 -> padded 16
    pc, blocks = _registered_donor(a, toks16, budget=16)
    assert a.num_available == 2                       # cache pins 2 of 4

    sch = ContinuousScheduler(max_batch=2, bucket=8)
    sch.submit(toks16, max_new_tokens=7)              # need 24 -> raw 3 blocks
    assert sch.next_fit_blocks(a, max_tokens=64) is None   # raw 3 > 2: refused
    got = sch.next_fit_blocks(a, max_tokens=64, prefix_cache=pc)
    assert got is not None                            # effective 2 <= 2: admitted
    # effective need = raw 3 - 2 adopted + 1 pending-COW spare = 2
    plan = pc.plan_admission(np.asarray(toks16, np.int32), 24)
    assert (plan.skip, plan.adopt, plan.adopt_partial) == (15, blocks, True)
    assert plan.raw_blocks == 3 and plan.reserve_blocks == 2


def test_admission_evicts_cold_entries_but_keeps_own_match():
    """A head short on blocks evicts cache-only entries oldest-first, but
    never the blocks its own match adopts."""
    a = BlockAllocator(5, block_size=8)
    cold = np.asarray(list(range(51, 67)), np.int32)  # unrelated old prefix
    pc, cold_blocks = _registered_donor(a, cold, budget=16)
    hot = np.asarray(list(range(1, 17)), np.int32)
    t = BlockTable(a, 16)
    t.ensure_tokens(16)
    pc.register(hot, t.blocks)
    hot_blocks = list(t.blocks)
    t.free()
    assert a.num_available == 0                       # all 4 blocks cache-held

    sch = ContinuousScheduler(max_batch=2, bucket=8)
    sch.submit(hot.tolist(), max_new_tokens=7)        # raw 3, effective 2
    got = sch.next_fit_blocks(a, max_tokens=64, prefix_cache=pc)
    assert got is not None
    assert all(a.refcount(b) == 0 for b in cold_blocks)   # cold run evicted
    assert all(a.refcount(b) >= 1 for b in hot_blocks)    # match protected
    assert pc.evicted_blocks == 2


# ---------------------------------------------------------------------------
# kernel parity: prefix-group paged attention
# ---------------------------------------------------------------------------
def _prefix_case(key, B, C, Hq, Hkv, hd, bs, nb, N, dtype=jnp.float32):
    """Random q/pages/new-kv; rows 0 and 1 share their 2 leading table
    entries (one prefix group), row 2+ stay private."""
    k = jax.random.PRNGKey(key)
    ks = jax.random.split(k, 5)
    q = jax.random.normal(ks[0], (B, C, Hq, hd), dtype)
    kp = jax.random.normal(ks[1], (N, bs, Hkv, hd), dtype)
    vp = jax.random.normal(ks[2], (N, bs, Hkv, hd), dtype)
    kn = jax.random.normal(ks[3], (B, C, Hkv, hd), dtype)
    vn = jax.random.normal(ks[4], (B, C, Hkv, hd), dtype)
    tables = np.arange(1, B * nb + 1).reshape(B, nb)
    tables[1, :2] = tables[0, :2]                     # rows 0/1 share 2 blocks
    assert tables.max() < N
    reps = np.arange(B, dtype=np.int32)
    nsh = np.zeros((B,), np.int32)
    reps[1], nsh[1] = 0, 2
    return (q, kp, vp, jnp.asarray(tables, jnp.int32), kn, vn,
            jnp.asarray(reps), jnp.asarray(nsh))


@pytest.mark.parametrize("B,C,Hq,Hkv,hd,bs,nb", [
    (3, 1, 4, 2, 16, 4, 3),       # plain decode, GQA, 3 rows / 1 group
    (2, 5, 4, 4, 8, 4, 4),        # chunk append spanning pages, MHA
    (3, 1, 4, 4, 16, 4, 3),       # decode, MHA over several kv heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefix_kernel_matches_ref_and_plain_paged(B, C, Hq, Hkv, hd, bs, nb,
                                                   dtype):
    """The group-indirected path must agree with its jnp oracle AND with
    plain paged attention on the rows' own tables — shared entries are
    identical physical ids, so the indirection is a pure re-routing.

    Writes start past the shared region (``pos >= 2 * bs``): shared
    blocks are read-only by the engine's COW contract — a write into one
    would race between the group's rows in any implementation."""
    q, kp, vp, tables, kn, vn, reps, nsh = _prefix_case(
        3, B, C, Hq, Hkv, hd, bs, nb, B * nb + 2, dtype)
    pos = jnp.asarray([bs * 2 + i for i in range(B)], jnp.int32)
    assert bs * 2 + B - 1 + C <= nb * bs              # writes stay in-table
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    out_plain, k_plain, v_plain = ref.paged_attention_ref(
        q, kp, vp, tables, kn, vn, pos, scale=hd ** -0.5)
    out_r, k_r, v_r = ref.prefix_paged_attention_ref(
        q, kp, vp, tables, kn, vn, pos, reps, nsh, scale=hd ** -0.5)
    # oracle vs plain paged: exact (same physical reads, same order)
    np.testing.assert_array_equal(np.asarray(out_r), np.asarray(out_plain))
    out_p, k_p, v_p = prefix_paged_attention(
        q, kp, vp, tables, kn, vn, pos, reps, nsh, scale=hd ** -0.5,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_array_equal(np.asarray(k_p)[1:], np.asarray(k_r)[1:])
    np.testing.assert_array_equal(np.asarray(v_p)[1:], np.asarray(v_r)[1:])


@pytest.mark.parametrize("window,is_global,softcap", [
    (6, False, 0.0), (0, True, 25.0),
])
def test_prefix_kernel_masks(window, is_global, softcap):
    q, kp, vp, tables, kn, vn, reps, nsh = _prefix_case(
        11, 3, 1, 4, 2, 16, 4, 3, 11)
    pos = jnp.asarray([9, 9, 5], jnp.int32)
    out_r, _, _ = ref.prefix_paged_attention_ref(
        q, kp, vp, tables, kn, vn, pos, reps, nsh, is_global,
        scale=16 ** -0.5, softcap=softcap, window=window)
    out_p, _, _ = prefix_paged_attention(
        q, kp, vp, tables, kn, vn, pos, reps, nsh, is_global,
        scale=16 ** -0.5, softcap=softcap, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)


def _page_walk(q, kp, vp, tables, kn, vn, pos):
    """``paged_attention``'s page-walk kernel (its chunk path) at C=1."""
    B, nb = tables.shape

    def page_map(b, j, t, *_):
        return (t[b, j], 0, 0, 0)

    def row_map(b, j, *_):
        return (b, 0, 0, 0)

    call = pa._paged_call(pa._paged_kernel, (page_map, row_map), 3, (B, nb), nb, (),
                          q, kp, vp, kn, vn, q.shape[-1] ** -0.5, 0.0, 0, True)
    return call(tables, pos, jnp.ones((1,), jnp.int32), q, kp, vp, kn, vn)[0]


def test_ops_prefix_dispatch_and_identity_groups():
    """ops.decode_attention routes prefix_groups to the prefix kernels
    (both backends) and an identity grouping reproduces the plain path
    bit-exactly; prefix_groups without a paged cache is rejected."""
    q, kp, vp, tables, kn, vn, reps, nsh = _prefix_case(
        17, 3, 1, 4, 2, 16, 4, 3, 11)
    pos = jnp.asarray([9, 9, 5], jnp.int32)
    groups = jnp.stack([reps, nsh])
    ident = jnp.stack([jnp.arange(3, dtype=jnp.int32),
                       jnp.zeros((3,), jnp.int32)])
    for backend, key in (("ref", "decode.ref_prefix"),
                         ("pallas", "decode.pallas_prefix")):
        ops.reset_dispatch_counts()
        o_g, _, _ = ops.decode_attention(q, kp, vp, kn, vn, pos,
                                         block_tables=tables,
                                         prefix_groups=groups,
                                         scale=16 ** -0.5, backend=backend)
        o_i, _, _ = ops.decode_attention(q, kp, vp, kn, vn, pos,
                                         block_tables=tables,
                                         prefix_groups=ident,
                                         scale=16 ** -0.5, backend=backend)
        o_plain, _, _ = ops.decode_attention(q, kp, vp, kn, vn, pos,
                                            block_tables=tables,
                                            scale=16 ** -0.5, backend=backend)
        assert ops.DISPATCH_COUNTS.get(key, 0) == 2
        if backend == "pallas":
            # the prefix kernel is the chunk path's page walk read through
            # the group indirection: with identity groups it is that walk
            # bit for bit; plain single-token decode has its own kernel,
            # which sums the same terms in another order
            np.testing.assert_array_equal(
                np.asarray(o_i), np.asarray(_page_walk(q, kp, vp, tables, kn, vn, pos)))
            np.testing.assert_allclose(np.asarray(o_i), np.asarray(o_plain),
                                       atol=2e-5, rtol=2e-5)
        else:
            np.testing.assert_array_equal(np.asarray(o_i), np.asarray(o_plain))
        np.testing.assert_allclose(np.asarray(o_g), np.asarray(o_plain),
                                   atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="paged cache"):
        ops.decode_attention(q, jnp.zeros((3, 24, 2, 16)),
                             jnp.zeros((3, 24, 2, 16)), kn, vn, pos,
                             prefix_groups=groups, backend="ref")


# ---------------------------------------------------------------------------
# engine end-to-end: cache on vs off, token-exact (null mesh)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def moe_setup():
    cfg = reduced("deepseek-moe-16b", capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _session(cfg):
    return HAPSession(cfg, "a6000", 1, source=fixed_plan("TP1", "TP1"),
                      prompt_bucket=16, gen_bucket=8)


def test_engine_rejects_prefix_cache_without_paging(moe_setup):
    cfg, params = moe_setup
    with pytest.raises(ValueError, match="paged"):
        _session(cfg).engine(params, paged=False, prefix_cache=True)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_engine_prefix_cache_token_exact(moe_setup, backend):
    """Greedy serve_continuous with the prefix cache on must reproduce
    the cache-off tokens exactly, while actually sharing: a donor and two
    identical-prompt followers on a pool too small for three raw
    admissions — the followers adopt the donor's registered blocks, skip
    their covered chunks, fork the tail on divergence (COW) and decode
    through the prefix-group kernel path."""
    cfg, params = moe_setup
    shared = list(range(1, 21))                       # 20 tokens -> padded 32
    reqs = [(shared + [40, 41], 6), (shared + [40, 41], 4),
            (shared + [40, 41], 4)]

    outs = {}
    for pc in (False, True):
        ops.reset_dispatch_counts()
        eng = _session(cfg).engine(params, max_batch=3, prefill_chunk=8,
                                   kv_block_size=8, kv_blocks=9,
                                   kernel_backend=backend, prefix_cache=pc)
        for p, g in reqs:
            eng.submit(Request(prompt=p, max_new_tokens=g))
        outs[pc] = [c.tokens for c in sorted(eng.serve_continuous(),
                                             key=lambda c: c.uid)]
        if pc:
            st = eng.stats
            # both followers adopt all 4 prompt blocks, skip 31 positions
            # each, and fork the partially-shared tail exactly once
            assert st.prefix_hit_blocks == 8 and st.prefix_hit_tokens == 62
            assert st.cow_copies == 2
            assert st.effective_block_need < st.raw_block_need
            key = ("decode.pallas_prefix" if backend == "pallas"
                   else "decode.ref_prefix")
            assert ops.DISPATCH_COUNTS.get(key, 0) > 0
    assert outs[True] == outs[False]


def test_engine_prefix_cache_tp2_subprocess():
    """The TP2 heads-sharded mesh variant: prefix cache on vs off must be
    token-exact under kernel_backend="pallas", with the shard_map'ed
    prefix kernel actually dispatched (DISPATCH_COUNTS), and on vs solo
    runs on the same mesh. Subprocess: forced host devices."""
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(root, "src"))
    code = textwrap.dedent("""
        import dataclasses, jax, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.core import HAPSession
        from repro.core.hap import fixed_plan
        from repro.kernels import ops as kernel_ops
        from repro.models import init_params
        from repro.serving import Request

        cfg = dataclasses.replace(get_config('deepseek-moe-16b').reduced(),
                                  dtype='float32', capacity_factor=8.0)
        params = init_params(cfg, jax.random.PRNGKey(0))
        mesh = Mesh(np.array(jax.devices()).reshape(1, 2),
                    ('data', 'model'))

        def session():
            return HAPSession(cfg, 'a6000', 2,
                              source=fixed_plan('TP2', 'TP2'), mesh=mesh,
                              prompt_bucket=16, gen_bucket=8)

        shared = list(range(1, 21))
        reqs = [(shared + [40, 41], 6), (shared + [40, 41], 4),
                (shared + [40, 41], 4)]
        solo = []
        for p, g in reqs:
            e1 = session().engine(params, max_batch=1)
            e1.submit(Request(prompt=p, max_new_tokens=g))
            solo.append(e1.run()[0].tokens)
        for backend in ('ref', 'pallas'):
            outs = {}
            for pc in (False, True):
                kernel_ops.reset_dispatch_counts()
                eng = session().engine(params, max_batch=3, prefill_chunk=8,
                                       kv_block_size=8, kv_blocks=9,
                                       kernel_backend=backend,
                                       prefix_cache=pc)
                for p, g in reqs:
                    eng.submit(Request(prompt=p, max_new_tokens=g))
                outs[pc] = [c.tokens
                            for c in sorted(eng.serve_continuous(),
                                            key=lambda c: c.uid)]
                if pc:
                    assert eng.stats.prefix_hit_blocks > 0
                    assert eng.stats.cow_copies > 0
                    counts = dict(kernel_ops.DISPATCH_COUNTS)
                    if backend == 'pallas':
                        assert counts.get(
                            'decode.pallas_prefix_shard_map', 0) > 0, counts
                        assert counts.get('decode.ref_prefix', 0) == 0, counts
                    else:
                        assert counts.get('decode.ref_prefix', 0) > 0, counts
            assert outs[True] == outs[False] == solo, (backend, outs, solo)
        print('OK')
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert "OK" in r.stdout, r.stdout + r.stderr
