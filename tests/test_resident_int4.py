"""Resident-INT4 expert serving (DESIGN.md §5b): the structured
last-dim-grouped quantization layout, the ``QuantizedExpert`` pytree,
fused per-shard dequant through the grouped-matmul seam (dispatch
``gmm.pallas_shard_map_int4`` under TP expert plans), the packed
transition path, and the engine serving resident packed weights
token-exactly against an fp engine holding the same quantized values.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from conftest import reduced
from repro.core.quantization import (
    dequantize_int4,
    pick_group_size,
    quantize_int4,
    quantize_int4_lastdim,
)
from repro.core.transition import TransitionExecutor
from repro.kernels import ops
from repro.models import init_params
from repro.models import moe as moe_mod
from repro.sharding.specs import KernelShardAxes, make_plan, quantized_pspec
from repro.serving.engine import InferenceEngine, Request
from repro.serving.sampling import SamplingParams

EXPERT_LEAVES = ("wi_gate", "wi_up", "wo")


def _mesh():
    devs = jax.devices()
    return Mesh(np.array(devs).reshape(len(devs)), ("model",))


def _quantize_expert(w, preferred=128):
    qt = quantize_int4_lastdim(np.asarray(w, np.float32),
                               pick_group_size(w.shape[-1], preferred))
    return ops.QuantizedExpert(packed=jnp.asarray(qt.packed),
                               scales=jnp.asarray(qt.scales),
                               zeros=jnp.asarray(qt.zeros))


# ---------------------------------------------------------------------------
# structured quantization layout
# ---------------------------------------------------------------------------
def test_pick_group_size():
    assert pick_group_size(256) == 128
    assert pick_group_size(96) == 96      # largest even divisor <= 128
    assert pick_group_size(40, 16) == 10  # 16 does not divide 40
    assert pick_group_size(128, 32) == 32
    with pytest.raises(ValueError):
        pick_group_size(7)                # no even divisor


def test_structured_packing_is_a_reshape_of_per_group():
    """Last-dim grouping == flat per_group grouping in row-major order:
    the structured layout is exactly a reshape, so dequant is bit-exact
    between the flat transition format and the resident format."""
    w = np.random.default_rng(0).normal(size=(3, 5, 64)).astype(np.float32)
    flat = quantize_int4(w, "per_group", 32)
    structured = quantize_int4_lastdim(w, 32)
    np.testing.assert_array_equal(
        np.asarray(structured.packed).reshape(np.asarray(flat.packed).shape),
        np.asarray(flat.packed))
    np.testing.assert_array_equal(
        np.asarray(dequantize_int4(structured)),
        np.asarray(dequantize_int4(flat)).reshape(w.shape))


def test_quantized_expert_pytree_derived_shape_and_scan():
    """QuantizedExpert carries NO static aux: shape/group_size derive
    from the packed leaf, so lax.scan slicing the leading (layer) axis
    yields per-layer QuantizedExperts with the right derived shape."""
    w = np.random.default_rng(1).normal(size=(2, 4, 8, 64)).astype(np.float32)
    qe = _quantize_expert(w, 32)
    assert qe.group_size == 32
    assert qe.shape == (2, 4, 8, 64)
    assert qe.ndim == 4
    assert qe.nbytes < w.nbytes // 3  # ~4x residency

    def body(carry, layer_qe):
        assert layer_qe.shape == (4, 8, 64)  # derived after slicing
        return carry + 1, layer_qe.packed.sum()

    n, _ = jax.lax.scan(body, 0, qe)
    assert int(n) == 2
    # leading-axis gather (the replication slot map) keeps leaves aligned
    picked = jax.tree_util.tree_map(lambda a: a[jnp.asarray([1, 0])], qe)
    assert isinstance(picked, ops.QuantizedExpert)
    assert picked.shape == (2, 4, 8, 64)


def test_quantized_pspec_moves_last_dim_to_group_axis():
    from jax.sharding import PartitionSpec as P

    assert quantized_pspec(P(None, "ep", None, None)) == P(
        None, "ep", None, None, None)
    assert quantized_pspec(P(None, None, None, "tp")) == P(
        None, None, None, "tp", None)


# ---------------------------------------------------------------------------
# grouped-matmul seam: dense vs resident-packed parity, fused shard_map
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_grouped_matmul_quantized_expert_parity(backend):
    """A QuantizedExpert rhs serves within quantization error of the
    dense weight, and bit-close to the dense round-tripped weight."""
    E, C, d, f = 2, 16, 32, 64
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    lhs = jax.random.normal(k1, (E, C, d), jnp.float32)
    dense = jax.random.normal(k2, (E, d, f), jnp.float32) * 0.1
    qe = _quantize_expert(dense, 32)
    rt = jnp.asarray(dequantize_int4(quantize_int4_lastdim(
        np.asarray(dense), 32)), jnp.float32)
    got = ops.grouped_matmul(lhs, qe, backend=backend)
    want_rt = ops.grouped_matmul(lhs, rt, backend=backend)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_rt),
                               atol=1e-5, rtol=1e-5)
    want_dense = ops.grouped_matmul(lhs, dense, backend=backend)
    # Round-to-nearest INT4 moves each weight by at most half its group's
    # scale, so output (e, c, j) moves by at most sum_d |lhs[e,c,d]| *
    # scale[e,d,group(j)] / 2 — the exact worst case, not a fitted
    # tolerance (a fixed atol is crossed by the tail of the summed error).
    # The 1e-5 slack covers f32 rounding in the two matmuls only.
    scale = np.repeat(np.asarray(qe.scales)[..., 0], qe.group_size, axis=-1)
    bound = 0.5 * np.einsum("ecd,edf->ecf", np.abs(np.asarray(lhs)), scale)
    err = np.abs(np.asarray(got) - np.asarray(want_dense))
    assert (err <= bound + 1e-5).all(), float((err - bound).max())


@pytest.mark.parametrize("sharded_dim", ["out", "in"])
def test_grouped_matmul_fused_shard_map_int4(sharded_dim):
    """Under a dividing TP axis the packed rhs goes INTO the shard_map
    (group axis sharded column-parallel, contraction dim row-parallel)
    and dequant runs per shard — dispatch gmm.pallas_shard_map_int4 —
    matching the global-dequant reference."""
    mesh = _mesh()
    n = mesh.shape["model"]
    E, C, d, f = 2, 16, 8 * n, 16 * n
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    lhs = jax.random.normal(k1, (E, C, d), jnp.float32)
    dense = jax.random.normal(k2, (E, d, f), jnp.float32) * 0.1
    qe = _quantize_expert(dense, 8)  # n_groups = f/8 divides any CI axis
    assert qe.packed.shape[-2] % n == 0 and qe.packed.shape[1] % n == 0
    ops.reset_dispatch_counts()
    got = ops.grouped_matmul(lhs, qe, shard_axes=KernelShardAxes(mesh, "model"),
                             sharded_dim=sharded_dim, backend="pallas")
    assert ops.DISPATCH_COUNTS["gmm.pallas_shard_map_int4"] == 1
    want = ops.grouped_matmul(lhs, qe, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_expert_ffn_tp_plan_resident_int4():
    """The full expert FFN under a TP plan with resident packed weights:
    all three grouped matmuls fuse the dequant per shard."""
    mesh = _mesh()
    cfg = reduced("deepseek-moe-16b", capacity_factor=8.0)
    plan = make_plan(mesh, cfg, expert_mode="tp")
    E, C, d, f = 4, 16, cfg.d_model, cfg.moe_d_ff
    if f % mesh.shape["model"]:
        pytest.skip("d_ff does not divide the mesh axis")
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    buf = jax.random.normal(ks[0], (E, C, d), jnp.float32)
    dense = {
        "wi_gate": jax.random.normal(ks[1], (E, d, f), jnp.float32) * 0.05,
        "wi_up": jax.random.normal(ks[2], (E, d, f), jnp.float32) * 0.05,
        "wo": jax.random.normal(ks[3], (E, f, d), jnp.float32) * 0.05,
    }
    q = {k: _quantize_expert(v, 16) for k, v in dense.items()}
    ops.reset_dispatch_counts()
    got = moe_mod.expert_ffn(buf, q["wi_gate"], q["wi_up"], q["wo"],
                             cfg.activation, plan=plan, backend="pallas")
    assert ops.DISPATCH_COUNTS["gmm.pallas_shard_map_int4"] == 3
    want = moe_mod.expert_ffn(buf, q["wi_gate"], q["wi_up"], q["wo"],
                              cfg.activation, plan=plan, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# transition path: structured backups
# ---------------------------------------------------------------------------
def test_backup_packed_restore_packed_roundtrip():
    tx = TransitionExecutor()
    w = np.random.default_rng(2).normal(size=(2, 3, 8, 64)).astype(np.float32)
    tx.backup_packed("moe/wi_gate", w, 32)
    qe = tx.restore_packed("moe/wi_gate")
    assert isinstance(qe, ops.QuantizedExpert)
    assert qe.shape == w.shape
    # the resident leaves hold exactly the quantizer's values: restoring
    # and dequantizing is bit-identical to an offline round trip
    np.testing.assert_array_equal(
        np.asarray(ops._dequant_weight(qe, ops.KernelBackend.REF,
                                       jnp.float32)),
        np.asarray(dequantize_int4(quantize_int4_lastdim(w, 32))))


def test_restore_packed_rejects_flat_backup():
    tx = TransitionExecutor()
    tx.backup("moe/wo", np.ones((4, 256), np.float32))
    with pytest.raises(ValueError, match="flat"):
        tx.restore_packed("moe/wo")


# ---------------------------------------------------------------------------
# engine: resident serving end to end
# ---------------------------------------------------------------------------
def _roundtrip_params(params, leaves=EXPERT_LEAVES):
    rt = dict(params)
    layers = dict(rt["layers"])
    moe = dict(layers["moe"])
    for name in leaves:
        w = np.asarray(moe[name], np.float32)
        gs = pick_group_size(w.shape[-1], 128)
        moe[name] = jnp.asarray(
            dequantize_int4(quantize_int4_lastdim(w, gs)), moe[name].dtype)
    layers["moe"] = moe
    rt["layers"] = layers
    return rt


def _serve(eng, prompts, gen=4):
    for p in prompts:
        eng.submit(Request(p, max_new_tokens=gen))
    return [c.tokens for c in eng.run(SamplingParams(temperature=0.0))]


@pytest.fixture(scope="module")
def moe_setup():
    cfg = dataclasses.replace(reduced("deepseek-moe-16b"),
                              capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_resident_int4_token_exact_vs_roundtrip_fp(moe_setup):
    """Greedy serving from resident packed weights == fp serving of the
    SAME quantized values, token for token: the fused dequant path adds
    no error beyond the quantizer's own (the documented tolerance)."""
    cfg, params = moe_setup
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]
    eng_q = InferenceEngine(cfg, params, max_batch=4, resident_int4=True)
    moe_q = eng_q.params["layers"]["moe"]
    for name in EXPERT_LEAVES:
        assert isinstance(moe_q[name], ops.QuantizedExpert)
    toks_q = _serve(eng_q, prompts)
    eng_fp = InferenceEngine(cfg, _roundtrip_params(params), max_batch=4)
    assert toks_q == _serve(eng_fp, prompts)
    assert eng_q.stats.resident_bytes_saved > 0


def test_engine_resident_residency_math(moe_setup):
    """Within the budget that holds E dense experts, the packed format
    holds strictly more — the capacity online replication spends."""
    cfg, params = moe_setup
    eng = InferenceEngine(cfg, params, max_batch=2, resident_int4=True)
    moe_q = eng.params["layers"]["moe"]
    moe_fp = params["layers"]["moe"]
    n_inst = moe_fp["wi_gate"].shape[0] * moe_fp["wi_gate"].shape[1]
    dense = sum(moe_fp[n].nbytes for n in EXPERT_LEAVES) / n_inst
    packed = sum(moe_q[n].nbytes for n in EXPERT_LEAVES) / n_inst
    budget = dense * cfg.n_routed_experts
    assert int(budget // packed) > cfg.n_routed_experts


def test_engine_resident_int4_transitions_stay_packed(moe_setup):
    """Both Eq.-6 mechanisms keep the resident leaves packed (no dense
    materialization) and serving stays token-identical after a
    transition round-trip."""
    cfg, params = moe_setup
    prompts = [[5, 6, 7, 8]]
    eng = InferenceEngine(cfg, params, max_batch=2, resident_int4=True)
    before = _serve(eng, prompts)
    for mech in ("int4_upload", "reshard"):
        eng._relayout_experts(mech, None)
        moe = eng.params["layers"]["moe"]
        for name in EXPERT_LEAVES:
            assert isinstance(moe[name], ops.QuantizedExpert), mech
    assert _serve(eng, prompts) == before


def test_engine_resident_int4_requires_moe():
    cfg = reduced("mistral-nemo-12b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="MoE"):
        InferenceEngine(cfg, params, resident_int4=True)
