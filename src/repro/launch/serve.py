"""Serving launcher: adaptive HAP-planned inference over the scheduler.

Demonstrates the ``HAPSession`` loop end to end: requests from two
workload buckets (short-prompt and long-prompt) drain as separate
batches; the engine re-plans per batch through the session's plan cache
and logs the Eq.-6 transition at the bucket boundary.

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-moe-16b \
      --layers 4 --prompt-len 512 --gen 32 --requests 8 --continuous

By default the planned config runs at its own dtype on the devices JAX
reports (a (1, n) ("data", "model") mesh for n > 1), with the planner's
chip model derived from the accelerator; ``--layers`` cuts its depth.
``--reduced`` runs the tiny float32 variant instead — the CPU and test
path, where ``--chip`` names the hardware to plan for:

  PYTHONPATH=src python -m repro.launch.serve --reduced --chip a6000 \
      --devices 1 --requests 6 --batch 3 --gen 8

``--source`` swaps the strategy source: the ILP planner (default), the
static TP/EP baselines, or a pinned plan via --plan
"attn=TP4,prefill=EP4,decode=TP4".

``--continuous`` serves the same trace through the continuous-batching
loop (decode-time joins, DESIGN.md §4b) instead of lockstep static
batches: re-planning then hooks at admission time on the live workload
bucket, and join/retire events are logged per request.

``--kernel-backend`` pins the serving kernels ("ref" jnp math, or
"pallas" for the flash/paged-attention/grouped-matmul kernels — run per
shard via shard_map under sharded plans; "auto" picks per platform) —
DESIGN.md §Kernel backends.

``--prefix-cache`` (continuous only) turns on prompt-prefix KV block
sharing (DESIGN.md §4d): matched prefixes are adopted copy-on-write,
their prefill chunks skipped, and per-run hit/COW/effective-need
counters are printed after the drain.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging

import jax
import numpy as np
from jax.sharding import AxisType

from repro.configs import get_config
from repro.core import HAPSession, Workload
from repro.core.latency import cached_latency_model
from repro.core.session import round_up
from repro.launch.runtime import planner_chip, use_compile_cache
from repro.models import init_params, param_shardings
from repro.serving import Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-moe-16b")
    ap.add_argument("--chip", default=None,
                    help="planner chip model (default: from the TPU's "
                         "device kind; required on other platforms)")
    ap.add_argument("--devices", type=int, default=None,
                    help="devices to plan for and run on (default: all "
                         "JAX reports)")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut of the served config (0 = all layers)")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny float32 variant of --arch (the "
                         "CPU and test path); planning stays full-scale")
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--source", default="ilp",
                    choices=["ilp", "tp", "ep", "fixed"])
    ap.add_argument("--plan", default="",
                    help='pinned plan, e.g. "attn=TP4,prefill=EP4,decode=TP4"'
                         " (implies --source fixed)")
    ap.add_argument("--prompt-bucket", type=int, default=64,
                    help="padding/planning bucket for prompt lengths")
    ap.add_argument("--uniform", action="store_true",
                    help="single workload bucket (disable the mixed "
                         "short/long demo)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (decode-time joins) instead "
                         "of lockstep static batches")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="continuous: chunked-prefill size in tokens "
                         "(0 = one chunk per prompt bucket)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="continuous: paged KV block size in tokens")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="continuous: paged KV pool size in blocks "
                         "(0 = worst-case auto-size). Undersized pools "
                         "raise actionable OutOfBlocks naming this flag")
    ap.add_argument("--kv-overcommit", type=float, default=0.0,
                    help="continuous: optimistic admission — charge only "
                         "this fraction of the output budget at admission "
                         "(0 = off, worst-case reservation). Overflow is "
                         "covered by preemption-by-recompute (DESIGN.md "
                         "§4f); outputs stay token-exact under greedy")
    ap.add_argument("--max-preemptions", type=int, default=3,
                    help="continuous: per-request preemption cap before a "
                         "request stops being victim-eligible")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline in ms from submission "
                         "(0 = none); expired requests retire with "
                         "status='deadline' at the next step boundary")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="continuous: share prompt-prefix KV blocks "
                         "across requests (refcounted, copy-on-write; "
                         "admission charges the post-sharing block need "
                         "— DESIGN.md §4d)")
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "ref", "pallas"],
                    help="serving kernel backend: prefill flash, decode "
                         "attention and grouped expert matmuls (auto "
                         "resolves per platform: Pallas on TPU, jnp ref "
                         "elsewhere)")
    ap.add_argument("--resident-int4", action="store_true",
                    help="serve the expert FFN weights as resident INT4 "
                         "pytrees (packed nibbles + per-group scales stay "
                         "on device; dequant fuses into grouped_matmul — "
                         "DESIGN.md §5b)")
    ap.add_argument("--replicate-experts", type=int, default=0,
                    help="extra hot-expert replica budget for online "
                         "replication (0 = off); replicas are granted by "
                         "routing frequency and rebalanced through the "
                         "Eq.-6 transition path")
    ap.add_argument("--rebalance-interval", type=int, default=32,
                    help="decode steps between replication re-plans")
    ap.add_argument("--prefetch", action="store_true",
                    help="predictive expert prefetch: pull the predicted "
                         "next batch of expert weights (per-(layer,expert) "
                         "INT4 restore rows) on the background worker "
                         "during decode windows, so restore barriers "
                         "consume staged rows instead of paying the full "
                         "host dequant (DESIGN.md §5c)")
    ap.add_argument("--prefetch-top-p", type=float, default=0.5,
                    help="predictor mass: per layer, prefetch the smallest "
                         "set of experts covering this predicted routing "
                         "probability")
    ap.add_argument("--moe-pipeline", type=int, default=0,
                    help="EP micro-batch pipeline depth K: the dispatch "
                         "buffer splits into K capacity chunks so each "
                         "chunk's all_to_all overlaps the previous chunk's "
                         "expert FFN (0 = auto from capacity, 1 = serial)")
    ap.add_argument("--no-async-transitions", action="store_true",
                    help="block on INT4 expert restores instead of running "
                         "them on the background worker overlapped with "
                         "prefill")
    args = ap.parse_args()
    logging.basicConfig(
        level=logging.INFO, format="%(name)s: %(message)s")
    use_compile_cache()

    full_cfg = get_config(args.arch)
    if args.layers:
        full_cfg = dataclasses.replace(full_cfg, num_layers=args.layers)
    if args.source == "fixed" and not args.plan:
        ap.error("--source fixed requires --plan")
    devices = jax.devices()
    n_dev = args.devices or len(devices)
    if n_dev > len(devices):
        ap.error(f"--devices {n_dev}: JAX reports {len(devices)}")
    chip = args.chip
    if chip is None:
        if devices[0].platform != "tpu":
            ap.error(f"--chip is required on {devices[0].platform}")
        chip = planner_chip(devices[0])
    mesh = (jax.make_mesh((1, n_dev), ("data", "model"),
                          devices=devices[:n_dev],
                          axis_types=(AxisType.Auto,) * 2)
            if n_dev > 1 else None)
    source = args.plan if args.plan else (
        None if args.source == "ilp" else args.source)
    session = HAPSession(full_cfg, chip, n_dev, source=source,
                         model=cached_latency_model(chip), mesh=mesh,
                         prompt_bucket=args.prompt_bucket,
                         gen_bucket=max(args.gen, 1))

    # mixed workloads: first half short prompts, second half long — two
    # buckets, so the engine re-plans at the boundary. The long bucket is
    # capped at --prompt-len (floored at one bucket + 1 so a second bucket
    # always exists), and long lengths are drawn from long_hi's own bucket
    # only (no straddle when --prompt-len is not a bucket multiple).
    long_hi = min(args.prompt_bucket * 4,
                  max(args.prompt_bucket + 1, args.prompt_len))

    # headline prediction for the long-bucket workload actually served
    w = Workload(batch=max(args.batch, 1),
                 prompt=round_up(long_hi, args.prompt_bucket), gen=args.gen)
    plan = session.plan_for(w)
    print(f"HAP: {plan.describe()}")
    t_tp = session.planner.evaluate(session.planner.tp_plan(), w)
    t_hap = session.planner.evaluate(plan, w)
    print(f"predicted speedup vs static TP: {t_tp / t_hap:.2f}x "
          f"(ILP {plan.ilp_time*1e3:.0f} ms)")

    cfg = (dataclasses.replace(full_cfg.reduced(), dtype="float32")
           if args.reduced else full_cfg)
    # created in place on the mesh, in the headline plan's first layout
    layout = plan.to_sharding_plan(
        mesh, cfg, phase="decode" if args.continuous else "prefill")
    params = init_params(cfg, jax.random.PRNGKey(0),
                         shardings=param_shardings(cfg, layout)
                         if mesh is not None else None)
    if args.prefix_cache and not args.continuous:
        ap.error("--prefix-cache requires --continuous (paged serving)")
    if args.kv_overcommit and not args.continuous:
        ap.error("--kv-overcommit requires --continuous (paged serving)")
    engine = session.engine(params, cfg=cfg, max_batch=args.batch,
                            kv_block_size=args.kv_block_size,
                            kv_blocks=args.kv_blocks or None,
                            kv_overcommit=args.kv_overcommit or None,
                            max_preemptions=args.max_preemptions,
                            prefill_chunk=args.prefill_chunk or None,
                            prefix_cache=args.prefix_cache,
                            resident_int4=args.resident_int4,
                            replicate_experts=args.replicate_experts,
                            rebalance_interval=args.rebalance_interval,
                            prefetch=args.prefetch,
                            prefetch_top_p=args.prefetch_top_p,
                            moe_pipeline=args.moe_pipeline,
                            async_transitions=not args.no_async_transitions,
                            kernel_backend=None if args.kernel_backend == "auto"
                            else args.kernel_backend)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        long_req = (not args.uniform) and i >= args.requests // 2
        hi = long_hi if long_req else args.prompt_bucket
        lo = max(1, (hi - 1) // args.prompt_bucket * args.prompt_bucket + 1)
        n = int(rng.integers(lo, hi + 1))
        engine.submit(Request(prompt=rng.integers(
            1, cfg.vocab_size, n).tolist(), max_new_tokens=args.gen,
            deadline_ms=args.deadline_ms or None))
    done = engine.serve_continuous() if args.continuous else engine.run()
    total_tok = sum(len(c.tokens) for c in done)
    st = engine.stats
    if args.continuous:
        print(f"served {len(done)} requests, {total_tok} tokens: "
              f"{st.joins} joins over {st.decode_steps} decode steps, "
              f"{st.prefill_chunks} prefill chunks ({st.fused_steps} "
              f"fused; {st.batches} live-batch generations)")
        if args.prefix_cache:
            print(f"prefix cache: {st.prefix_hit_blocks} blocks / "
                  f"{st.prefix_hit_tokens} tokens adopted, "
                  f"{st.cow_copies} COW forks, effective block need "
                  f"{st.effective_block_need} vs raw {st.raw_block_need}")
    else:
        print(f"served {len(done)} requests, {total_tok} tokens in "
              f"{st.batches} batches")
    if args.kv_overcommit:
        print(f"optimistic admission: {st.preemptions} preemptions "
              f"({st.preempted_tokens} tokens recomputed, "
              f"{st.prefix_evictions_on_pressure} prefix evictions under "
              f"pressure)")
    terminal = st.cancelled + st.deadline_expired
    if terminal:
        print(f"lifecycle: {st.cancelled} cancelled, "
              f"{st.deadline_expired} deadline-expired")
    if st.background_errors or st.planner_fallbacks:
        print(f"degraded paths: {st.background_errors} background errors "
              f"({st.prefetch_errors} prefetch, {st.restore_errors} "
              f"restore, {st.replication_search_errors} replication "
              f"search), {st.planner_fallbacks} planner fallbacks")
    print(f"plan changes: {st.replans} (strategy switches "
          f"{st.plan_switches}, cache hits {st.cache_hits}), "
          f"transition total {st.transition_ms_total:.1f} ms")
    if st.async_restores:
        print(f"async restore: {st.async_restores} kicked, "
              f"{st.restore_overlap_ms:.1f} ms overlapped prefill, "
              f"{st.restore_wait_ms:.1f} ms exposed at the barrier")
    if args.resident_int4:
        print(f"resident INT4 experts: "
              f"{st.resident_bytes_saved / 2**20:.2f} MiB residency freed")
    if args.prefetch:
        print(f"expert prefetch: {st.prefetch_predicted} rows predicted, "
              f"{st.prefetch_hits} hit / {st.prefetch_misses} missed at "
              f"restore barriers, {st.prefetch_bytes / 2**20:.2f} MiB "
              f"pulled ({st.prefetch_hidden_ms:.1f} ms hidden, "
              f"{st.prefetch_exposed_ms:.1f} ms exposed)")
    if args.replicate_experts:
        rep = engine._replication
        print(f"expert replication: {st.replication_rebalances} rebalances "
              f"over {st.routing_steps} tracked steps, degrees "
              f"{rep.degrees if rep is not None else 'uniform'}")


if __name__ == "__main__":
    main()
