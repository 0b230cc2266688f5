"""The main-path Pallas kernels compile for a TPU v5e chip at
DeepSeek-MoE-16B widths (16 heads x 128, 16-token pages, d_model 2048,
experts of 1408), against a described ``v5e:2x2`` topology: no chip is
needed, and the chip's own compiler rules on block tiling, casts and
VMEM. Interpret-mode tests cannot see any of that."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.int4_dequant import int4_dequant
from repro.kernels.mla_attention import mla_decode_attention
from repro.kernels.paged_attention import paged_attention, prefix_paged_attention

H, HD, BS = 16, 128, 16  # q = kv heads, head_dim, KV page tokens
D, F = 2048, 1408  # d_model, expert d_ff
SLOTS, POOL, WIDTH = 8, 281, 64  # live rows, pool pages, table width
CHUNK = 512  # the engine's default prefill chunk (one prompt bucket)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rule on
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _paged(B, C, hq=H, hkv=H, width=WIDTH, pool=POOL):
    def fn(q, kp, vp, tables, kn, vn, pos):
        return paged_attention(q, kp, vp, tables, kn, vn, pos, interpret=False)

    return fn, [((B, C, hq, HD), jnp.bfloat16), ((pool, BS, hkv, HD), jnp.bfloat16),
                ((pool, BS, hkv, HD), jnp.bfloat16), ((B, width), jnp.int32),
                ((B, C, hkv, HD), jnp.bfloat16), ((B, C, hkv, HD), jnp.bfloat16),
                ((B,), jnp.int32)]


def _prefix():
    fn, shapes = _paged(SLOTS, 1)

    def prefix(q, kp, vp, tables, kn, vn, pos, reps, nsh):
        return prefix_paged_attention(q, kp, vp, tables, kn, vn, pos, reps,
                                      nsh, interpret=False)

    return prefix, shapes + [((SLOTS,), jnp.int32)] * 2


def _flash():
    def fn(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    return fn, [((1, H, CHUNK, HD), jnp.bfloat16)] * 3


def _gmm(C):
    def fn(lhs, rhs):
        return grouped_matmul(lhs, rhs, interpret=False)

    return fn, [((64, C, D), jnp.bfloat16), ((64, D, F), jnp.bfloat16)]


def _dequant():
    groups = D * F // 32  # one expert, group size 32

    def fn(packed, scales, zeros):
        return int4_dequant(packed, scales, zeros, interpret=False)

    return fn, [((groups, 16), jnp.uint8), ((groups, 1), jnp.float32),
                ((groups, 1), jnp.float32)]


def _mla():
    """DeepSeek-V2-Lite's absorbed decode: 32 rows over 800-page tables
    (12,800 positions), a 5-layer latent pool of 25,601 pages, 16 heads,
    512 + 64 latent values a row held 640 lanes wide."""
    def fn(q, pool, tables, pos, layer):
        return mla_decode_attention(q, pool, tables, pos, layer, kv_rank=512,
                                    scale=0.114721, interpret=False)

    return fn, [((32, H, 640), jnp.bfloat16), ((5, 25601, BS, 640), jnp.bfloat16),
                ((32, 800), jnp.int32), ((32,), jnp.int32), ((), jnp.int32)]


CASES = {
    "paged_decode": lambda: _paged(SLOTS, 1),
    "paged_chunk": lambda: _paged(1, CHUNK),
    # single-token decode at the benchmark cells' widths: 64 rows, tables
    # 160 pages wide (2560 positions), Mixtral-8x7B's 32 q / 8 kv heads
    # and DeepSeek-MoE-16B's 16 / 16
    "paged_decode_mixtral": lambda: _paged(64, 1, hq=32, hkv=8, width=160, pool=10241),
    "paged_decode_deepseek": lambda: _paged(64, 1, width=160, pool=10241),
    "prefix_paged_decode": _prefix,
    "flash_s512": _flash,
    "gmm_c8": lambda: _gmm(8),
    "gmm_c200": lambda: _gmm(200),
    "int4_dequant_expert": _dequant,
    "mla_decode_v2lite": _mla,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
