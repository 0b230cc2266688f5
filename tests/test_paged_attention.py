"""Paged-attention kernel + kernel-backend seam validation.

Three altitudes: the Pallas kernel against its pure-jnp oracle
(interpret=True on CPU), the unified ``ops.decode_attention`` entry
point across backends and cache layouts, and the serving engine
end-to-end under ``kernel_backend="pallas"`` — token-exact greedy
equivalence vs solo reference runs on the null mesh (the TP2 mesh
variant lives in tests/test_kv_cache.py as a subprocess test).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced
from repro.core import HAPSession
from repro.core.hap import fixed_plan
from repro.kernels import ops, ref
from repro.kernels import paged_attention as pa
from repro.kernels.paged_attention import paged_attention
from repro.models import init_params
from repro.serving import Request


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


def _case(key, B, C, Hq, Hkv, hd, bs, nb, N, dtype=jnp.float32):
    """Random q/pages/new-kv plus disjoint per-row block tables."""
    q = _rand(key, (B, C, Hq, hd), dtype)
    kp = _rand(key + 1, (N, bs, Hkv, hd), dtype)
    vp = _rand(key + 2, (N, bs, Hkv, hd), dtype)
    kn = _rand(key + 3, (B, C, Hkv, hd), dtype)
    vn = _rand(key + 4, (B, C, Hkv, hd), dtype)
    blocks = np.arange(1, B * nb + 1).reshape(B, nb)
    assert blocks.max() < N, "pool too small for disjoint tables"
    return q, kp, vp, kn, vn, jnp.asarray(blocks, jnp.int32)


# decode rows of a table several loop steps wide whose width is no
# multiple of the pages a step walks: query positions on the last slot of
# the first step, the first slot of the second, the table's last slot,
# and the first and last slots of a page inside a step
_STEPS_BS, _STEPS_NB = 16, 20
_STEPS_POS = (127, 128, 319, 16, 31)


@pytest.mark.parametrize("B,C,Hq,Hkv,hd,bs,nb,at", [
    (2, 1, 4, 2, 16, 8, 3, None),      # plain decode, GQA
    (1, 8, 2, 2, 32, 4, 4, None),      # chunk append spanning pages, MHA
    (3, 4, 4, 1, 16, 8, 2, None),      # MQA
    (2, 5, 8, 4, 8, 4, 3, None),       # uneven chunk vs block size
    (3, 1, 4, 4, 16, 8, 3, None),      # decode, MHA over several kv heads
    #                                    (G=1, DeepSeek-MoE's shape)
    (5, 1, 4, 2, 16, _STEPS_BS, _STEPS_NB, _STEPS_POS),  # decode, steps
    (2, 1, 32, 8, 128, 16, 3, (31, 16)),   # decode, Mixtral's GQA widths
    (2, 1, 16, 16, 128, 16, 3, (47, 0)),   # decode, DeepSeek's G=1 widths
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_matches_ref(B, C, Hq, Hkv, hd, bs, nb, at, dtype):
    q, kp, vp, kn, vn, tables = _case(0, B, C, Hq, Hkv, hd, bs, nb,
                                      B * nb + 2, dtype)
    if at is None:
        # rows at distinct depths; every write range stays inside the table
        at = [(3 + 5 * i) % (nb * bs - C) for i in range(B)]
    if nb == _STEPS_NB:
        P = pa._pages_per_step(bs, nb)
        assert 1 < P < nb and nb % P, "the case must span ragged loop steps"
        assert at[0] == P * bs - 1 and at[1] == P * bs
    pos = jnp.asarray(at, jnp.int32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    out_r, k_r, v_r = ref.paged_attention_ref(q, kp, vp, tables, kn, vn, pos,
                                              scale=hd ** -0.5)
    out_p, k_p, v_p = paged_attention(q, kp, vp, tables, kn, vn, pos,
                                      scale=hd ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=tol, rtol=tol)
    # updated pages must agree exactly outside the trash block
    np.testing.assert_array_equal(np.asarray(k_p)[1:], np.asarray(k_r)[1:])
    np.testing.assert_array_equal(np.asarray(v_p)[1:], np.asarray(v_r)[1:])


@pytest.mark.parametrize("window,is_global,softcap", [
    (6, False, 0.0), (6, True, 0.0), (0, True, 25.0), (6, False, 25.0),
])
def test_paged_kernel_masks(window, is_global, softcap):
    q, kp, vp, kn, vn, tables = _case(7, 2, 4, 4, 2, 16, 8, 3, 10)
    pos = jnp.asarray([9, 2], jnp.int32)
    out_r, _, _ = ref.paged_attention_ref(
        q, kp, vp, tables, kn, vn, pos, is_global,
        scale=16 ** -0.5, softcap=softcap, window=window)
    out_p, _, _ = paged_attention(
        q, kp, vp, tables, kn, vn, pos, is_global,
        scale=16 ** -0.5, softcap=softcap, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window,is_global,softcap", [
    (6, False, 0.0), (6, True, 0.0), (0, True, 25.0), (300, False, 25.0),
])
def test_paged_decode_kernel_masks(window, is_global, softcap):
    """Single-token decode over tables several loop steps wide: a sliding
    window that leaves whole steps behind a row, softcap, and the layer
    flag traced."""
    q, kp, vp, kn, vn, tables = _case(19, 3, 1, 4, 2, 16, _STEPS_BS, _STEPS_NB,
                                      3 * _STEPS_NB + 1)
    pos = jnp.asarray([300, 257, 5], jnp.int32)

    @jax.jit
    def run(flag):
        return paged_attention(q, kp, vp, tables, kn, vn, pos, flag,
                               scale=16 ** -0.5, softcap=softcap, window=window,
                               interpret=True)

    out_r, k_r, _ = ref.paged_attention_ref(
        q, kp, vp, tables, kn, vn, pos, is_global,
        scale=16 ** -0.5, softcap=softcap, window=window)
    out_p, k_p, _ = run(jnp.asarray(is_global))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(k_p)[1:], np.asarray(k_r)[1:])


def test_paged_kernel_traced_is_global():
    """The sliding-window flag is a traced per-layer bool inside the model
    scan — the kernel must accept it as an operand, not a static."""
    q, kp, vp, kn, vn, tables = _case(11, 1, 2, 2, 2, 16, 4, 3, 5)
    pos = jnp.asarray([6], jnp.int32)

    @jax.jit
    def both(flag):
        o, _, _ = paged_attention(q, kp, vp, tables, kn, vn, pos, flag,
                                  scale=16 ** -0.5, window=4, interpret=True)
        return o

    for flag in (True, False):
        o_r, _, _ = ref.paged_attention_ref(
            q, kp, vp, tables, kn, vn, pos, flag,
            scale=16 ** -0.5, window=4)
        np.testing.assert_allclose(np.asarray(both(jnp.asarray(flag))),
                                   np.asarray(o_r), atol=2e-5, rtol=2e-5)


def test_paged_kernel_drained_row_leaves_live_pages_alone():
    """A drained slot (all-trash table, stale pos) must not perturb any
    live page: its writes land in the trash block only."""
    q, kp, vp, kn, vn, _ = _case(13, 2, 1, 2, 2, 16, 8, 3, 8)
    tables = jnp.asarray([[1, 2, 3], [0, 0, 0]], jnp.int32)  # row 1 drained
    pos = jnp.asarray([17, 4], jnp.int32)
    out_r, k_r, v_r = ref.paged_attention_ref(q, kp, vp, tables, kn, vn, pos,
                                              scale=2 ** -0.5)
    out_p, k_p, v_p = paged_attention(q, kp, vp, tables, kn, vn, pos,
                                      scale=2 ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out_p)[0], np.asarray(out_r)[0],
                               atol=2e-5, rtol=2e-5)  # live row agrees
    np.testing.assert_array_equal(np.asarray(k_p)[1:], np.asarray(k_r)[1:])
    # live pages of row 0 changed only at its write slot (17 -> block 3)
    np.testing.assert_array_equal(np.asarray(k_p)[1], np.asarray(kp)[1])
    assert not np.array_equal(np.asarray(k_p)[3], np.asarray(kp)[3])

    # a drained row between live rows of tables several loop steps wide,
    # its stale position deep in the table: it walks trash pages only
    q, kp, vp, kn, vn, tables = _case(14, 3, 1, 4, 2, 16, _STEPS_BS, _STEPS_NB,
                                      3 * _STEPS_NB + 1)
    tables = tables.at[1].set(0)
    pos = jnp.asarray([300, 290, 257], jnp.int32)
    out_r, k_r, v_r = ref.paged_attention_ref(q, kp, vp, tables, kn, vn, pos,
                                              scale=2 ** -0.5)
    out_p, k_p, v_p = paged_attention(q, kp, vp, tables, kn, vn, pos,
                                      scale=2 ** -0.5, interpret=True)
    assert np.isfinite(np.asarray(out_p)).all()
    np.testing.assert_allclose(np.asarray(out_p)[0::2], np.asarray(out_r)[0::2],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(k_p)[1:], np.asarray(k_r)[1:])
    np.testing.assert_array_equal(np.asarray(v_p)[1:], np.asarray(v_r)[1:])
    # every live page keeps its content but the two write slots
    live = np.asarray(tables)[0::2].ravel()
    written = {int(tables[0, 300 // _STEPS_BS]), int(tables[2, 257 // _STEPS_BS])}
    for page in live:
        if int(page) not in written:
            np.testing.assert_array_equal(np.asarray(k_p)[page], np.asarray(kp)[page])


@pytest.mark.parametrize("layout", ["contiguous_scalar", "contiguous_rows",
                                    "paged"])
def test_ops_decode_attention_backends_agree(layout):
    """The unified entry point serves both layouts from both backends."""
    B, Hq, Hkv, hd = 2, 4, 2, 16
    if layout == "paged":
        C = 4
        q, kc, vc, kn, vn, tables = _case(17, B, C, Hq, Hkv, hd, 4, 4, 10)
        pos = jnp.asarray([5, 0], jnp.int32)
        kw = dict(block_tables=tables)
    else:
        C = 4 if layout == "contiguous_scalar" else 1
        q = _rand(21, (B, C, Hq, hd), jnp.float32)
        kc = _rand(22, (B, 24, Hkv, hd), jnp.float32)
        vc = _rand(23, (B, 24, Hkv, hd), jnp.float32)
        kn = _rand(24, (B, C, Hkv, hd), jnp.float32)
        vn = _rand(25, (B, C, Hkv, hd), jnp.float32)
        pos = (jnp.asarray(7, jnp.int32) if layout == "contiguous_scalar"
               else jnp.asarray([7, 12], jnp.int32))
        kw = {}
    o_r, k_r, v_r = ops.decode_attention(q, kc, vc, kn, vn, pos,
                                         scale=hd ** -0.5, backend="ref", **kw)
    o_p, k_p, v_p = ops.decode_attention(q, kc, vc, kn, vn, pos,
                                         scale=hd ** -0.5, backend="pallas",
                                         **kw)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r),
                               atol=2e-5, rtol=2e-5)
    lo = 1 if layout == "paged" else 0  # skip the trash page
    np.testing.assert_array_equal(np.asarray(k_p)[lo:], np.asarray(k_r)[lo:])
    np.testing.assert_array_equal(np.asarray(v_p)[lo:], np.asarray(v_r)[lo:])


# ---------------------------------------------------------------------------
# engine end-to-end on the null mesh (TP2 variant: tests/test_kv_cache.py)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def moe_setup():
    cfg = reduced("deepseek-moe-16b", capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _session(cfg):
    return HAPSession(cfg, "a6000", 1, source=fixed_plan("TP1", "TP1"),
                      prompt_bucket=16, gen_bucket=8)


def test_engine_pallas_backend_token_exact(moe_setup):
    """serve_continuous under kernel_backend="pallas" (interpret mode on
    CPU) reproduces the ref backend's solo-run tokens exactly — the
    null-mesh acceptance bar for the kernel seam. The static run() loop
    rides along: its contiguous cache dispatches through the same entry
    point as a one-page-per-row pool."""
    cfg, params = moe_setup
    reqs = [([1, 2, 3, 4], 5), ([9, 8, 7], 4)]
    solo = []
    for p, g in reqs:
        # pin "ref" so this stays a cross-backend check even under the CI
        # kernels-interpret leg's REPRO_KERNEL_BACKEND=pallas env toggle
        e1 = _session(cfg).engine(params, max_batch=1, kernel_backend="ref")
        e1.submit(Request(prompt=p, max_new_tokens=g))
        solo.append(e1.run()[0].tokens)

    static = _session(cfg).engine(params, max_batch=1,
                                  kernel_backend="pallas")
    cont = _session(cfg).engine(params, max_batch=2, kv_block_size=8,
                                prefill_chunk=8, kernel_backend="pallas")
    assert static.kernel_backend == "pallas"
    for p, g in reqs:
        static.submit(Request(prompt=p, max_new_tokens=g))
        cont.submit(Request(prompt=p, max_new_tokens=g))
    got_static = [c.tokens for c in static.run()]
    got_cont = [c.tokens
                for c in sorted(cont.serve_continuous(), key=lambda c: c.uid)]
    assert got_static == solo
    assert got_cont == solo
    assert cont.stats.prefill_chunks >= 2
