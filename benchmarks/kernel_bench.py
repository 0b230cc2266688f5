"""Kernel micro-benchmarks (CPU host): jit-dispatch timing of the pure-jnp
reference paths (what the models execute off-TPU) + interpret-mode parity
checks for the Pallas TPU kernels. Wall-times on CPU are NOT TPU
performance — the TPU-side cost model lives in the roofline analysis.

Three sweeps land in the CI perf-trajectory artifact, each a gateable
ref-vs-pallas parity signal (CPU wall-times of an interpreted kernel are
diagnostic only):

- ``paged_decode``   — (block_size, max_blocks) over the fused
  append+attend step (``ops.decode_attention``),
- ``sharded_decode`` — the same step shard_map'ed over a mesh spanning
  every host device (the sharded-plan hot path; 1 device still executes
  the shard_map code path),
- ``grouped_matmul`` — the expert-FFN seam (``ops.grouped_matmul``)
  across fp32 / bf16 / INT4-dequant weights.

::

    PYTHONPATH=src python benchmarks/kernel_bench.py --out BENCH_kernel_bench.json
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.quantization import quantize_int4
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.int4_dequant import int4_dequant
from repro.sharding.specs import KernelShardAxes

try:
    from ._bench_io import write_bench_json
except ImportError:  # run as a plain script
    import os

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _bench_io import write_bench_json


def _time(fn, *args, iters=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _paged_case(B, C, Hq, Hkv, hd, block_size, max_blocks):
    """Disjoint per-row tables over a pool sized for the sweep point."""
    ks = jax.random.split(jax.random.PRNGKey(42), 5)
    pool = B * max_blocks + 1  # + trash block 0
    q = jax.random.normal(ks[0], (B, C, Hq, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (pool, block_size, Hkv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (pool, block_size, Hkv, hd), jnp.float32)
    kn = jax.random.normal(ks[3], (B, C, Hkv, hd), jnp.float32)
    vn = jax.random.normal(ks[4], (B, C, Hkv, hd), jnp.float32)
    tables = jnp.arange(1, B * max_blocks + 1, dtype=jnp.int32).reshape(
        B, max_blocks
    )
    pos = jnp.asarray(
        [(max_blocks * block_size) // 2 + i for i in range(B)], jnp.int32
    )
    return q, kp, vp, kn, vn, tables, pos


def paged_decode_bench(csv_rows, sweep=((8, 8), (16, 8), (16, 16), (32, 8))):
    """ref vs Pallas-interpret fused paged decode across the block sweep.

    Returns the JSON payload fragment for the perf-trajectory artifact:
    per sweep point, the per-call microseconds of both backends and the
    max |ref - pallas| parity error (the gateable correctness signal —
    CPU wall-times of an interpreted kernel are diagnostic only).
    """
    B, C, Hq, Hkv, hd = 4, 1, 8, 4, 64
    points = {}
    ok = True
    for block_size, max_blocks in sweep:
        args = _paged_case(B, C, Hq, Hkv, hd, block_size, max_blocks)
        label = f"bs{block_size}x{max_blocks}"

        def jitted(backend):
            # operands stay jit ARGUMENTS (baking them in as closure
            # constants would time constant-embedding, not the kernel)
            def fn(q, kp, vp, kn, vn, tables, pos):
                out, _, _ = ops.decode_attention(
                    q,
                    kp,
                    vp,
                    kn,
                    vn,
                    pos,
                    block_tables=tables,
                    scale=hd**-0.5,
                    backend=backend,
                )
                return out

            return jax.jit(fn)

        ref_fn, pal_fn = jitted("ref"), jitted("pallas")
        us_ref = _time(ref_fn, *args)
        us_pal = _time(pal_fn, *args)
        err = float(jnp.max(jnp.abs(ref_fn(*args) - pal_fn(*args))))
        ok &= err < 2e-4
        csv_rows.append(f"kernel_paged_decode_ref_jnp,{us_ref:.0f},{label}")
        csv_rows.append(
            f"kernel_paged_decode_pallas_interp,{us_pal:.0f},"
            f"{label}_max_err={err:.2e}"
        )
        points[label] = {
            "block_size": block_size,
            "max_blocks": max_blocks,
            "ref_us": us_ref,
            "pallas_interp_us": us_pal,
            "max_err": err,
        }
    return {"shape": f"B{B}C{C}H{Hq}/{Hkv}D{hd}", "points": points, "parity_ok": ok}


def sharded_decode_bench(csv_rows, sweep=((2, 8, 8), (4, 8, 8), (4, 16, 8))):
    """ref vs shard_map'ed Pallas decode on a mesh over every host device.

    Sweeps (kv_heads, block_size, max_blocks); q heads are 2x kv. The
    pallas backend runs the paged kernel per head shard under shard_map
    (``KernelShardAxes``), the ref backend the global scatter/gather —
    the parity error is the gateable signal that sharded plans and the
    single-shard oracle agree.
    """
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(len(devs)), ("model",))
    axes = KernelShardAxes(mesh, "model")
    B, C, hd = 4, 1, 64
    points = {}
    ok = True
    for hkv, block_size, max_blocks in sweep:
        hkv, hq = hkv * len(devs), 2 * hkv * len(devs)
        args = _paged_case(B, C, hq, hkv, hd, block_size, max_blocks)
        label = f"h{hq}/{hkv}bs{block_size}x{max_blocks}x{len(devs)}dev"

        def jitted(backend, shard_axes=None):
            def fn(q, kp, vp, kn, vn, tables, pos):
                out, _, _ = ops.decode_attention(
                    q,
                    kp,
                    vp,
                    kn,
                    vn,
                    pos,
                    block_tables=tables,
                    scale=hd**-0.5,
                    shard_axes=shard_axes,
                    backend=backend,
                )
                return out

            return jax.jit(fn)

        ref_fn = jitted("ref")
        pal_fn = jitted("pallas", shard_axes=axes)
        us_ref = _time(ref_fn, *args)
        us_pal = _time(pal_fn, *args)
        err = float(jnp.max(jnp.abs(ref_fn(*args) - pal_fn(*args))))
        ok &= err < 2e-4
        csv_rows.append(f"kernel_sharded_decode_ref_jnp,{us_ref:.0f},{label}")
        csv_rows.append(
            f"kernel_sharded_decode_pallas_shard_map,{us_pal:.0f},"
            f"{label}_max_err={err:.2e}"
        )
        points[label] = {
            "kv_heads": hkv,
            "block_size": block_size,
            "max_blocks": max_blocks,
            "ref_us": us_ref,
            "pallas_shard_map_us": us_pal,
            "max_err": err,
        }
    return {"devices": len(devs), "points": points, "parity_ok": ok}


def grouped_matmul_bench(csv_rows):
    """ref vs Pallas-interpret for the expert-FFN grouped-matmul seam
    across weight dtypes, including the INT4-dequant-aware path."""
    E, C, d, f = 8, 128, 256, 128
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    points = {}
    ok = True
    dense32 = jax.random.normal(k2, (E, d, f), jnp.float32)
    qt = quantize_int4(np.asarray(dense32), "per_group", group_size=128)
    cases = {
        "fp32": (jnp.float32, dense32),
        "bf16": (jnp.bfloat16, dense32.astype(jnp.bfloat16)),
        "int4": (
            jnp.float32,
            ops.QuantizedWeight(
                packed=jnp.asarray(qt.packed),
                scales=jnp.asarray(qt.scales),
                zeros=jnp.asarray(qt.zeros),
                shape=(E, d, f),
            ),
        ),
    }
    for label, (lhs_dtype, rhs) in cases.items():
        lhs = jax.random.normal(k1, (E, C, d), lhs_dtype)

        def jitted(backend):
            return jax.jit(lambda ll: ops.grouped_matmul(ll, rhs, backend=backend))

        ref_fn, pal_fn = jitted("ref"), jitted("pallas")
        us_ref = _time(ref_fn, lhs)
        us_pal = _time(pal_fn, lhs)
        err = float(
            jnp.max(
                jnp.abs(
                    ref_fn(lhs).astype(jnp.float32) - pal_fn(lhs).astype(jnp.float32)
                )
            )
        )
        tol = 2e-1 if lhs_dtype == jnp.bfloat16 else 2e-3
        ok &= err < tol
        csv_rows.append(f"kernel_gmm_seam_ref_{label},{us_ref:.0f},E{E}C{C}")
        csv_rows.append(
            f"kernel_gmm_seam_pallas_{label},{us_pal:.0f},max_err={err:.2e}"
        )
        points[label] = {"ref_us": us_ref, "pallas_interp_us": us_pal, "max_err": err}
    return {"shape": f"E{E}C{C}K{d}F{f}", "points": points, "parity_ok": ok}


def run(csv_rows, payload=None):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 8, 512, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 4, 512, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 4, 512, 64), jnp.float32)
    ref_attn = jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c))
    us = _time(ref_attn, q, k, v)
    csv_rows.append(f"kernel_attention_ref_jnp,{us:.0f},B1H8S512D64")
    out_p = flash_attention(q, k, v, bq=128, bk=128, interpret=True)
    err = float(jnp.max(jnp.abs(out_p - ref.flash_attention_ref(q, k, v))))
    csv_rows.append(f"kernel_attention_pallas_interp,0,max_err={err:.2e}")

    lhs = jax.random.normal(ks[0], (8, 256, 512), jnp.float32)
    rhs = jax.random.normal(ks[1], (8, 512, 256), jnp.float32)
    us = _time(jax.jit(ref.grouped_matmul_ref), lhs, rhs)
    csv_rows.append(f"kernel_gmm_ref_jnp,{us:.0f},E8C256K512F256")
    out_g = grouped_matmul(lhs, rhs, bc=128, bf=128, bk=256, interpret=True)
    err = float(jnp.max(jnp.abs(out_g - ref.grouped_matmul_ref(lhs, rhs))))
    csv_rows.append(f"kernel_gmm_pallas_interp,0,max_err={err:.2e}")

    pk = jax.random.randint(ks[0], (1024, 64), 0, 256, jnp.int32).astype(jnp.uint8)
    sc = jax.random.uniform(ks[1], (1024, 1), jnp.float32, 0.01, 0.2)
    zp = jax.random.uniform(ks[2], (1024, 1), jnp.float32, -1, 1)
    us = _time(jax.jit(lambda a, b, c: ref.int4_dequant_ref(a, b, c)), pk, sc, zp)
    csv_rows.append(f"kernel_dequant_ref_jnp,{us:.0f},G1024gs128")
    out_d = int4_dequant(pk, sc, zp, interpret=True)
    err = float(
        jnp.max(
            jnp.abs(
                out_d.astype(jnp.float32)
                - ref.int4_dequant_ref(pk, sc, zp).astype(jnp.float32)
            )
        )
    )
    csv_rows.append(f"kernel_dequant_pallas_interp,0,max_err={err:.2e}")

    paged = paged_decode_bench(csv_rows)
    sharded = sharded_decode_bench(csv_rows)
    gmm = grouped_matmul_bench(csv_rows)
    if payload is not None:
        payload["paged_decode"] = paged
        payload["sharded_decode"] = sharded
        payload["grouped_matmul"] = gmm
    return paged["parity_ok"] and sharded["parity_ok"] and gmm["parity_ok"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--out", default="BENCH_kernel_bench.json", help="JSON artifact path"
    )
    args = ap.parse_args()
    rows = ["name,us_per_call,derived"]
    payload = {"backend_default": ops.default_backend().value}
    ok = run(rows, payload=payload)
    payload["rows"] = rows
    payload["parity_ok"] = ok
    print("\n".join(rows))
    write_bench_json(args.out, payload)
    print(f"wrote {args.out}")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
