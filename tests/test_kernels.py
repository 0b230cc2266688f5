"""Pallas kernel validation: shape/dtype sweeps, interpret=True on CPU,
assert_allclose against the pure-jnp oracles in repro.kernels.ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.int4_dequant import int4_dequant
from repro.kernels import ops


def _tol(dt):
    return 2e-2 if dt == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Sk,hd", [
    (2, 4, 2, 64, 64, 32),
    (1, 8, 8, 128, 128, 64),
    (2, 4, 1, 64, 128, 32),
    (1, 2, 2, 32, 32, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, Hq, Hkv, Sq, Sk, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, hd), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, hd), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, hd), dtype)
    out = flash_attention(q, k, v, bq=32, bk=32, interpret=True)
    expect = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (True, 16, 0.0), (True, 0, 30.0), (False, 0, 0.0),
    (True, 16, 50.0),
])
def test_flash_attention_masks(causal, window, softcap):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 64, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 64, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 64, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, bq=16, bk=16, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                     softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_matches_model_attention():
    """Kernel agrees with the model's chunked-jnp attention path."""
    from repro.models.attention import full_attention
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(name="t", family="dense", num_heads=4, num_kv_heads=2,
                      head_dim=32, d_model=128, dtype="float32",
                      rope_theta=0.0)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, S = 2, 64
    q = jax.random.normal(ks[0], (B, S, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, 2, 32), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    model_out = full_attention(q, k, v, cfg, True, pos, pos, kv_chunk=16)
    kern_out = flash_attention(q.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3),
                               bq=16, bk=16,
                               interpret=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(kern_out), np.asarray(model_out),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("E,C,d,f", [
    (4, 64, 128, 64), (2, 128, 256, 128), (8, 32, 64, 32), (1, 16, 32, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul(E, C, d, f, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    lhs = jax.random.normal(k1, (E, C, d), dtype)
    rhs = jax.random.normal(k2, (E, d, f), dtype)
    out = grouped_matmul(lhs, rhs, bc=16, bf=16, bk=32, interpret=True)
    expect = ref.grouped_matmul_ref(lhs, rhs)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=_tol(dtype) * d ** 0.5, rtol=2e-2)


@pytest.mark.parametrize("G,gs,bg", [(16, 64, 8), (128, 32, 32), (8, 256, 8)])
@pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32])
def test_int4_dequant(G, gs, bg, out_dtype):
    key = jax.random.PRNGKey(3)
    pk = jax.random.randint(key, (G, gs // 2), 0, 256,
                            jnp.int32).astype(jnp.uint8)
    sc = jax.random.uniform(key, (G, 1), jnp.float32, 0.01, 0.2)
    zp = jax.random.uniform(key, (G, 1), jnp.float32, -1, 1)
    out = int4_dequant(pk, sc, zp, out_dtype=out_dtype, bg=bg,
                       interpret=True)
    expect = ref.int4_dequant_ref(pk, sc, zp, out_dtype=out_dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=1e-2)


def test_ops_dispatch_ref_equals_pallas():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (1, 2, 32, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 32, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 32, 16), jnp.float32)
    a = ops.attention(q, k, v, backend="ref")
    b = ops.attention(q, k, v, backend=ops.KernelBackend.PALLAS)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_backend_resolution(monkeypatch):
    """None/"auto" -> env toggle -> per-platform default; bad specs raise."""
    monkeypatch.delenv(ops.BACKEND_ENV, raising=False)
    assert ops.default_backend() == ops.KernelBackend.REF  # CPU test host
    assert ops.resolve_backend(None) == ops.default_backend()
    assert ops.resolve_backend("auto") == ops.default_backend()
    assert ops.resolve_backend("pallas") == ops.KernelBackend.PALLAS
    assert ops.resolve_backend(ops.KernelBackend.REF) == ops.KernelBackend.REF
    monkeypatch.setenv(ops.BACKEND_ENV, "pallas")
    assert ops.resolve_backend(None) == ops.KernelBackend.PALLAS
    assert ops.resolve_backend("ref") == ops.KernelBackend.REF  # explicit wins
    with pytest.raises(ValueError):
        ops.resolve_backend("cuda")
