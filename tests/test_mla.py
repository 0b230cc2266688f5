"""Multi-head latent attention (DeepSeek-V2-Lite), YaRN positions and the
leading dense layer on the CPU: YaRN's frequencies and softmax scale at the
published settings; the absorbed Pallas decode kernel (interpret mode)
against its oracle and against attention in the decompressed head space,
at ragged lengths across pages and loop steps; the engine's chunked
prefill and decode through the latent pool against the float32 reference
(``repro.models.reference``) by logits, with the dense layer present; and
every existing configuration's parameter tree as it was before the new
fields (``data/param_trees.json``, captured from the code before them)."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import HAPSession, fixed_plan
from repro.kernels import ops, ref
from repro.kernels.mla_attention import mla_decode_attention
from repro.models import init_paged_cache, init_params, param_shapes
from repro.models import attention as attn_mod
from repro.models.common import Yarn, rope_frequencies, yarn_correction_range
from repro.models.reference import reference_logits
from repro.models.transformer import forward_hidden, unembed
from repro.serving import Request

V2 = get_config("deepseek-v2-lite")
DATA = Path(__file__).parent / "data"
PARENT = json.loads((DATA / "param_trees.json").read_text())


def tiny_mla(**kw):
    """V2-Lite's layout at a CPU size, float32: a dense first layer, two
    MoE layers, YaRN that ramps inside the rope block, and a cos/sin
    factor other than 1 (mscale 1 over mscale_all_dim 0.707)."""
    return dataclasses.replace(
        V2, name="tiny-mla", num_layers=3, d_model=64, vocab_size=128,
        num_heads=4, num_kv_heads=4, head_dim=24, d_ff=96, n_routed_experts=4,
        n_shared_experts=1, top_k=2, moe_d_ff=32, shared_d_ff=32, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        yarn_original_max_position=16, yarn_mscale=1.0, dtype="float32",
        capacity_factor=2.0, **kw)


# -- YaRN ------------------------------------------------------------------
def test_yarn_frequencies_follow_the_correction_range_at_v2_lite():
    y = attn_mod._yarn(V2)
    assert y == Yarn(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    assert yarn_correction_range(64, 10000.0, y) == (10, 23)
    base = np.asarray(rope_frequencies(64, 10000.0))
    got = np.asarray(rope_frequencies(64, 10000.0, y))
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)  # original
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)  # interpolated
    for i in range(11, 23):  # linear in the pair index between
        t = (i - 10) / 13
        np.testing.assert_allclose(got[i], base[i] / 40 * t + base[i] * (1 - t), rtol=1e-6)


def test_softmax_scale_at_v2_lite():
    assert V2.head_dim == 192
    assert attn_mod._scale(V2) == pytest.approx(0.114721, abs=1e-6)
    # without YaRN's all-dim factor the plain 1/sqrt(head_dim) stays
    plain = dataclasses.replace(V2, yarn_mscale_all_dim=0.0)
    assert attn_mod._scale(plain) == pytest.approx(192 ** -0.5)


# -- the absorbed decode kernel -----------------------------------------------
R, DR, W, BS, NB, H = 64, 16, 128, 16, 80, 4  # 80 pages: 3 loop steps of 32


@pytest.mark.parametrize("pos", [[0, 15, 16, 511], [512, 700, 1279, 1024],
                                 [1, 1000, 33, 47]])
def test_absorbed_kernel_matches_decompressed_attention(pos):
    """f32 latent rows at ragged positions (inside the first page, on page
    and loop-step boundaries, the table's last position), each row's pages
    scattered over the pool of layer 1 of 2: the Pallas kernel equals its
    oracle, and absorbing W_UK into the queries and W_UV after the sum
    equals attention with each head's key and value decompressed."""
    B = len(pos)
    k = jax.random.split(jax.random.PRNGKey(7), 6)
    c = jax.random.normal(k[0], (B, NB * BS, R))
    k_pe = jax.random.normal(k[1], (B, NB * BS, DR))
    w_uk = jax.random.normal(k[2], (R, H, 32)) / 8
    w_uv = jax.random.normal(k[3], (R, H, 32)) / 8
    q_nope = jax.random.normal(k[4], (B, H, 32))
    q_pe = jax.random.normal(k[5], (B, H, DR))
    rows = jnp.concatenate([c, k_pe, jnp.zeros((B, NB * BS, W - R - DR))], -1)
    perm = np.random.default_rng(0).permutation(B * NB) + 1
    tables = jnp.asarray(perm.reshape(B, NB), jnp.int32)
    pool = jnp.zeros((2, B * NB + 1, BS, W)).at[1, tables].set(rows.reshape(B, NB, BS, W))
    posv = jnp.asarray(pos, jnp.int32)
    scale = 0.2
    q_abs = jnp.concatenate([jnp.einsum("bhn,rhn->bhr", q_nope, w_uk), q_pe,
                             jnp.zeros((B, H, W - R - DR))], -1)
    got = mla_decode_attention(q_abs, pool, tables, posv, jnp.int32(1), kv_rank=R,
                               scale=scale, interpret=True)
    want = ref.mla_paged_attention_ref(q_abs, pool, tables, posv, 1, kv_rank=R,
                                       scale=scale)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the same attention with keys and values decompressed per head
    key = jnp.concatenate([jnp.einsum("btr,rhn->bthn", c, w_uk),
                           jnp.broadcast_to(k_pe[:, :, None], (B, NB * BS, H, DR))], -1)
    val = jnp.einsum("btr,rhv->bthv", c, w_uv)
    s = jnp.einsum("bhd,bthd->bht", jnp.concatenate([q_nope, q_pe], -1), key) * scale
    ok = jnp.arange(NB * BS)[None, None] <= posv[:, None, None]
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1)
    np.testing.assert_allclose(jnp.einsum("bhr,rhv->bhv", got, w_uv),
                               jnp.einsum("bht,bthv->bhv", p, val), rtol=2e-4, atol=2e-5)


# -- the engine against the reference ------------------------------------------
def _engine(cfg, params, backend, max_batch=2, **kw):
    session = HAPSession(cfg, "a6000", 1, source=fixed_plan("TP1", "TP1"),
                         fallback="", prompt_bucket=8, gen_bucket=8)
    return session.engine(params, max_batch=max_batch, kernel_backend=backend,
                          kv_block_size=4, **kw)


def _two_row_logits(eng, prompts, follow):
    """Logits of the engine's own step programs: each row's prompt in
    chunks of at most 8 through its block table (row 1's pages in reverse
    order), then decode steps of both rows together at their own
    positions, teacher-forced with ``follow``."""
    cfg, params = eng.cfg, eng.params
    plan = eng._sharding_for("decode")
    nb, bs = 8, 4
    cache = init_paged_cache(cfg, 2, 2 * nb + 1, bs, nb, dtype=params["embed"].dtype)
    tables = np.stack([np.arange(1, nb + 1), np.arange(2 * nb, nb, -1)]).astype(np.int32)
    cache = cache._replace(block_tables=jnp.asarray(tables))
    got = {}
    for row, p in enumerate(prompts):
        for lo in range(0, len(p), 8):
            toks = jnp.asarray(p[lo:lo + 8], jnp.int32)[None]
            logits, cache = eng._chunk_fn(plan)(params, toks, row, cache)
        got[(row, len(p) - 1)] = logits[0]
    step = eng._decode_fn(plan)
    for i in range(len(follow[0])):
        col = jnp.asarray([[f[i]] for f in follow], jnp.int32)
        logits, cache = step(params, col, cache)
        for row, p in enumerate(prompts):
            got[(row, len(p) + i)] = logits[row]
    return got


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("arch", ["mla", "gqa"])
def test_prefill_and_decode_match_the_reference_with_a_dense_first_layer(arch, backend):
    """Two rows at ragged lengths (prompts of 16 and 11 tokens in chunks
    of 8, across 4-token pages), then four decode steps of both: every
    logit equals the float32 reference's full forward pass at the same
    position, to float32 rounding. ``gqa`` is DeepSeek-MoE's attention
    with the same dense first layer."""
    if arch == "mla":
        cfg = tiny_mla()
    else:
        base = get_config("deepseek-moe-16b").reduced()
        cfg = dataclasses.replace(base, dtype="float32", num_layers=3,
                                  first_k_dense_replace=1, d_ff=96, capacity_factor=2.0)
    params = init_params(cfg, jax.random.PRNGKey(3))
    assert "dense_layers" in params
    ops.reset_dispatch_counts()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (16, 11)]
    follow = [rng.integers(1, cfg.vocab_size, 4).tolist() for _ in prompts]
    got = _two_row_logits(_engine(cfg, params, backend), prompts, follow)
    for row, p in enumerate(prompts):
        want = np.asarray(reference_logits(params, cfg, p + follow[row]))
        for (r, at), logits in got.items():
            if r == row:
                np.testing.assert_allclose(np.asarray(logits), want[at], atol=2e-5,
                                           err_msg=f"row {row} position {at}")
    if arch == "mla":
        fam = "mla_decode.pallas" if backend == "pallas" else "mla_decode.ref"
        assert ops.DISPATCH_COUNTS[fam] and ops.DISPATCH_COUNTS["mla_chunk.xla"]
        assert not any(k.startswith("decode.") for k in ops.DISPATCH_COUNTS)


def test_full_sequence_forward_matches_the_reference():
    """The training / full-sequence path (decompressed MLA over the whole
    sequence, the dense layer first) equals the reference."""
    cfg = tiny_mla()
    params = init_params(cfg, jax.random.PRNGKey(4))
    toks = np.random.default_rng(2).integers(1, cfg.vocab_size, 24)
    x = params["embed"][jnp.asarray(toks)][None]
    h, _, _ = forward_hidden(params, cfg, x, None)
    np.testing.assert_allclose(np.asarray(unembed(params, cfg, h))[0],
                               np.asarray(reference_logits(params, cfg, toks)), atol=2e-5)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Three ragged requests through ``serve_continuous`` on the Pallas
    backend (chunks of 8, fused chunk + decode steps), traced."""
    cfg = tiny_mla()
    params = init_params(cfg, jax.random.PRNGKey(6))
    eng = _engine(cfg, params, "pallas", max_batch=2, prefill_chunk=8)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (13, 5, 20)]
    for p in prompts:
        eng.submit(Request(prompt=p, max_new_tokens=5))
    ops.reset_dispatch_counts()
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("trace")))
    try:
        done = eng.serve_continuous()
    finally:
        jax.profiler.stop_trace()
    return eng, prompts, done, dict(ops.DISPATCH_COUNTS)


def test_served_tokens_are_the_reference_argmax(served):
    """Greedy tokens served through admission, fused steps and decode
    are the reference's best token at every position (prompts left-padded
    with 0 to the 8-token bucket, as the engine serves them) wherever its
    best two logits are not a near-tie."""
    eng, prompts, done, dispatch = served
    assert [len(c.tokens) for c in done] == [5, 5, 5]
    for c, p in zip(sorted(done, key=lambda c: c.uid), prompts):
        padded = [0] * (-len(p) % 8) + p
        seq = padded + c.tokens[:-1]
        ref_logits = np.asarray(reference_logits(eng.params, eng.cfg, seq))
        for i, t in enumerate(c.tokens):
            row = ref_logits[len(padded) - 1 + i]
            top2 = np.sort(row)[-2:]
            if top2[1] - top2[0] > 1e-3:
                assert t == int(np.argmax(row)), (c.uid, i)
    assert dispatch.get("mla_decode.pallas") and dispatch.get("mla_chunk.xla")
    assert dispatch.get("gmm.pallas")
    assert not [k for k, n in dispatch.items() if n and ".ref" in k]


def test_decode_steps_count_the_latent_pages_they_walk(served):
    eng = served[0]
    steps = [s.attrs for s in eng.trace.spans if s.name == "engine.step"]
    decoding = [a for a in steps if a["rows"]]
    assert decoding
    for a in decoding:
        assert a["rows"] <= a["kv_pages_live"] <= a["kv_pages_table"]


def test_latent_pool_holds_one_row_a_token_and_layer():
    """At V2-Lite's widths a token and layer is one 576-value row (1,152
    bytes of bf16), held 640 lanes wide; the pool has no separate V."""
    cfg = dataclasses.replace(V2, num_layers=5)
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 32, 101, 16, 800))
    assert cache.k.shape == (5, 101, 16, 640) and cache.v is None
    assert cfg.kv_lora_rank + cfg.qk_rope_head_dim == 576
    assert (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 2 == 1152


# -- existing configurations ---------------------------------------------------
@pytest.mark.parametrize("name", sorted(PARENT))
def test_existing_parameter_trees_are_unchanged(name):
    cfg = get_config(name)
    leaves = jax.tree.leaves_with_path(param_shapes(cfg),
                                       is_leaf=lambda x: isinstance(x, tuple))
    assert [[jax.tree_util.keystr(p), list(s)] for p, s in leaves] == PARENT[name]["leaves"]
    assert cfg.total_params() == PARENT[name]["total_params"]
    assert cfg.active_params_per_token() == PARENT[name]["active_params"]
    counts = cfg.param_counts()
    assert {k: counts[k] for k in PARENT[name]["param_counts"]} == PARENT[name]["param_counts"]


def test_v2_lite_parameters_match_the_published_size():
    assert V2.total_params() / 1e9 == pytest.approx(15.7, rel=0.01)
    five = dataclasses.replace(V2, num_layers=5)
    assert five.total_params() / 1e9 == pytest.approx(2.84, rel=0.01)
    shapes = param_shapes(five)
    assert shapes["dense_layers"]["0"]["ffn"]["wi_gate"] == (2048, 10944)
    assert shapes["layers"]["moe"]["wi_gate"] == (4, 64, 2048, 1408)
    assert shapes["layers"]["attn"] == {"wq": (4, 2048, 3072), "wkv_a": (4, 2048, 576),
                                        "kv_norm": (4, 512), "wkv_b": (4, 512, 4096),
                                        "wo": (4, 2048, 2048)}


# -- the planner's cost and memory model -------------------------------------
def test_planner_counts_the_latent_cache_and_the_absorbed_decode():
    """HAP's attention-module model at V2-Lite's widths: decode reads one
    1,152-byte latent row a position and layer (against 16 x 2 x 192 x 2
    for K and V of every head), attends in the latent space, and the
    memory check (Eq. 5) sees the latent cache and the dense first
    layer's FFN."""
    from repro.core import flops
    from repro.core.flops import Workload

    w = Workload(batch=32, prompt=6144, gen=3584)
    ctx = 6144 + 3584 / 2
    assert flops.kv_bytes_per_layer(V2, w, "decode") == 32 * ctx * 1152
    d, H = 2048, 16
    proj = 2.0 * 32 * d * (H * 192 + 576 + H * 128)
    absorb = 2.0 * 32 * H * 512 * 256
    assert flops.attn_flops(V2, w, "decode") == proj + absorb + 2.0 * 32 * ctx * H * 1088
    pre = flops.attn_flops(V2, w, "prefill")
    T = 32 * 6144
    assert pre == (2.0 * T * d * (H * 192 + 576 + H * 128) + 2.0 * T * 512 * H * 256
                   + 2.0 * T * 3072 * H * 320)
    m = flops.memory_terms(V2, w)
    assert m["kv"] == 27 * 32 * (6144 + 3584) * 1152
    c = V2.param_counts()
    assert m["exp"] == 2 * (26 * c["ffn_per_layer"] + 3 * 2048 * 10944)
    gqa = dataclasses.replace(V2, kv_lora_rank=0, head_dim=192)
    assert flops.memory_terms(gqa, w)["kv"] / m["kv"] == pytest.approx(16 * 2 * 192 / 576)


def test_scopes_name_the_latent_attention_in_the_compiled_programs():
    """The decode and chunk programs carry the MLA scopes in their ops'
    metadata, so a device trace's ops can be put down to the absorption,
    the latent attention, the decompression and the dense layer."""
    cfg = tiny_mla()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = _engine(cfg, params, "ref")
    plan = eng._sharding_for("decode")
    cache = init_paged_cache(cfg, 2, 17, 4, 8, dtype=params["embed"].dtype)
    tok = jnp.zeros((2, 1), jnp.int32)
    dec = eng._decode_fn(plan).lower(params, tok, cache).compile().as_text()
    chunk = eng._chunk_fn(plan).lower(params, jnp.zeros((1, 8), jnp.int32), 0,
                                      cache).compile().as_text()
    for scope in ("mla_absorb", "mla_attend", "kv_write", "dense_ffn"):
        assert f"/{scope}/" in dec, scope
    for scope in ("kv_b", "mla_attend", "kv_write", "dense_ffn"):
        assert f"/{scope}/" in chunk, scope
