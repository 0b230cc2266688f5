"""On-chip smoke test of HAP's serving path at DeepSeek-MoE-16B widths.

One chip (the default): ``HAPSession.engine`` serves 8 requests of
481..512 prompt tokens and 32 new tokens each with continuous batching
on the paged KV pool, the kernel backend left on auto (Pallas on a
TPU). The model is ``deepseek-moe-16b`` at its published widths in
bfloat16 with the depth cut to 4 layers, random weights from ``--seed``.
The engine's own jitted step functions then run one prompt through
prefill and 2 decode steps through the paged cache, and their logits are
checked against a float32 ``jax.numpy`` forward of the same weights.

Four chips (``--chips 4``): all 28 layers, created already sharded over a
(1, 4) ("data", "model") mesh, serve the same requests under the plan
the ILP picks for them and under static TP4, and the two plans' logits
are compared with each other. Nothing else runs in that phase.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed check, a host where JAX finds no TPU, or a directory without
the rest of this repository exits non-zero without that line.

    python chip_smoke.py [--seed N] [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "deepseek-moe-16b"
ONE_CHIP_LAYERS = 4
N_REQUESTS = 8
PROMPT_BUCKET = 512
PROMPT_MIN = 481  # 481..512-token prompts all pad to one bucket
GEN = 32
CHECK_STEPS = 6

# Logits are compared by relative L2 error per position: |got - want| /
# |want| over the whole vocabulary, at the last prompt position (two
# prefill paths) and after each decode step.
#
# Engine (bf16 weights, activations and KV cache) against the float32
# reference on the same bf16 weights: bf16 keeps 8 significant bits, and
# at these widths with 4 layers the error is ~2-3% at most positions
# (the median over a check measured 0.022-0.056 on the CPU backend, over
# 8 seeds). A position whose token has near-tied router scores can pick
# another expert than the float32 run at some layer, which moved single
# positions by up to 0.2 there. A wrong head, position or page moves
# every position by ~1 (two different positions' logits differ by
# 1.02-1.13). So the median must stay within bf16 noise and no single
# position may drift halfway to an unrelated one.
MEDIAN_REL_L2 = 0.1
MAX_REL_L2 = 0.5
# The ILP plan against static TP4 (both bf16, same weights) differ only
# in summation order across chips, which moves the hidden state less
# than bf16 rounding against float32 does; the same two bounds apply.


class SmokeFailure(Exception):
    """A check failed: the smoke exits non-zero without a result line."""


# -- checks (pure functions of what the run produced; tested on the CPU) ----
def check_device(devices, chips: int) -> None:
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "no device"
        raise SmokeFailure(f"no TPU: JAX reports {found}")
    if len(devices) < chips:
        raise SmokeFailure(f"{chips} chips asked for, JAX sees {len(devices)}")


def check_session(session) -> None:
    if session.fallback:
        raise SmokeFailure(
            f"session degrades planner failures to static "
            f"{session.fallback!r}: a solver crash would pass unseen"
        )


def check_stats(stats) -> None:
    if stats.background_errors or stats.planner_fallbacks:
        raise SmokeFailure(
            f"{stats.background_errors} background errors, "
            f"{stats.planner_fallbacks} planner fallbacks"
        )


def check_dispatch(counts, required) -> None:
    """Every required kernel family traced its Pallas branch, and no
    reference branch stood in for a kernel anywhere."""
    missing = [
        fam
        for fam in required
        if not any(k.startswith(fam + ".pallas") and n for k, n in counts.items())
    ]
    quiet = sorted(k for k, n in counts.items() if n and ".ref" in k)
    if missing or quiet:
        raise SmokeFailure(
            f"Pallas branch missing for {missing}, reference branches "
            f"traced: {quiet} (dispatch {dict(counts)})"
        )


def check_completions(done, n: int, gen: int, vocab: int) -> int:
    bad = [
        c.uid
        for c in done
        if c.status != "ok"
        or len(c.tokens) != gen
        or not all(0 <= t < vocab for t in c.tokens)
    ]
    if len(done) != n or bad:
        raise SmokeFailure(f"{len(done)}/{n} completions, malformed: {bad}")
    return sum(len(c.tokens) for c in done)


def rel_l2(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check_logits(label: str, got: dict, want: dict) -> dict:
    """Relative L2 logit error per position; raises when the median
    passes ``MEDIAN_REL_L2`` or any position passes ``MAX_REL_L2``."""
    import numpy as np

    errs = {k: rel_l2(got[k], want[k]) for k in want}
    med = float(np.median(list(errs.values())))
    print(f"{label}: median {med:.4g}; "
          + ", ".join(f"{k} {v:.4g}" for k, v in errs.items()))
    if not (med <= MEDIAN_REL_L2 and max(errs.values()) <= MAX_REL_L2):
        raise SmokeFailure(
            f"{label}: logits off by {errs} (limits: median {MEDIAN_REL_L2}, "
            f"any position {MAX_REL_L2})")
    return errs


def check_expert_spans(params, n: int) -> None:
    """Each expert weight is split over ``n`` devices, no device whole."""
    moe = params["layers"]["moe"]
    for name in ("wi_gate", "wi_up", "wo"):
        w = moe[name]
        shard = w.sharding.shard_shape(w.shape)
        if len(w.sharding.device_set) != n or w.size != n * math.prod(shard):
            raise SmokeFailure(f"{name} {w.shape} is not split over {n} devices")


# -- the run -------------------------------------------------------------
def smoke_config(num_layers):
    """``deepseek-moe-16b`` at its widths with a depth cut. The expert
    capacity is raised to E / top_k, so no expert can overflow and the
    engine routes dropless, as the published model does (and as the
    reference computes)."""
    import dataclasses

    from repro.configs import get_config

    cfg = get_config(ARCH)
    return dataclasses.replace(
        cfg,
        num_layers=num_layers or cfg.num_layers,
        capacity_factor=cfg.n_routed_experts / cfg.top_k,
    )


def make_requests(cfg, seed: int):
    import numpy as np

    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt=rng.integers(1, cfg.vocab_size, int(n)).tolist(),
            max_new_tokens=GEN,
        )
        for n in rng.integers(PROMPT_MIN, PROMPT_BUCKET + 1, N_REQUESTS)
    ]


def engine_logits(engine, prompt, follow) -> dict:
    """Logits of the engine's own jitted step functions for ``prompt``
    (one whole bucket, so no padding) and then the teacher-forced
    ``follow`` tokens: flash prefill (the engine's static path), the paged
    chunk prefill (its continuous path), then decode steps through that
    paged cache, sized as the serve sized its pool."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.session import round_up
    from repro.models import init_paged_cache
    from repro.serving.kv_cache import blocks_for

    cfg, params, S = engine.cfg, engine.params, len(prompt)
    toks = jnp.asarray(prompt, jnp.int32)[None, :]
    prefill = engine._prefill_fn(engine._sharding_for("prefill"))
    out = {"prefill_flash": prefill(params, {"tokens": toks}, S)[0][0]}
    plan = engine._sharding_for("decode")
    bs, nslots = engine.kv_block_size, engine.scheduler.max_batch
    need = S + GEN + 1
    max_blocks = blocks_for(round_up(need, engine.scheduler.bucket), bs)
    cache = init_paged_cache(
        cfg, nslots, nslots * blocks_for(need, bs) + 1, bs, max_blocks,
        dtype=params["embed"].dtype, plan=plan,
    )
    tables = np.zeros((nslots, max_blocks), np.int32)
    tables[0] = np.arange(1, max_blocks + 1)
    cache = cache._replace(block_tables=jnp.asarray(tables))
    logits, cache = engine._chunk_fn(plan)(params, toks, 0, cache)
    out["prefill_paged"] = logits[0]
    step = engine._decode_fn(plan)
    for i, t in enumerate(follow, 1):
        col = np.zeros((nslots, 1), np.int32)
        col[0] = t
        logits, cache = step(params, jnp.asarray(col), cache)
        out[f"decode_{i}"] = logits[0]
    return {k: np.asarray(v, np.float32) for k, v in out.items()}


def reference_at(params, cfg, prompt, follow, keys) -> dict:
    """The float32 reference's logits at the positions ``engine_logits``
    reports."""
    import numpy as np

    from repro.models.reference import reference_logits

    ref = np.asarray(reference_logits(params, cfg, list(prompt) + list(follow)))
    S = len(prompt)
    return {
        k: ref[S - 1 + (int(k.split("_")[1]) if k.startswith("decode") else 0)]
        for k in keys
    }


def serve(engine, requests) -> tuple:
    for r in requests:
        engine.submit(r)
    t0 = time.perf_counter()
    done = engine.serve_continuous()  # ends on host-side sampled tokens
    return done, time.perf_counter() - t0


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def one_chip(seed: int, device) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from repro.core import HAPSession, fixed_plan
    from repro.kernels import ops
    from repro.launch.runtime import planner_chip
    from repro.models import count_params, init_params

    cfg = smoke_config(ONE_CHIP_LAYERS)
    params = init_params(
        cfg, jax.random.PRNGKey(seed), shardings=SingleDeviceSharding(device)
    )
    n_params = count_params(params)
    print(f"config: {ARCH} at its widths, {cfg.dtype}; reduced: num_layers "
          f"{smoke_config(None).num_layers} -> {cfg.num_layers}; {n_params} "
          f"params ({n_params * jnp.dtype(cfg.dtype).itemsize / 1e9:.2f} GB); "
          f"capacity_factor {cfg.capacity_factor:.4g} (dropless)")
    # one chip has one plan: TP1 for attention and experts, so no
    # latency model is loaded or fitted
    session = HAPSession(
        cfg, planner_chip(device), 1, source=fixed_plan("TP1", "TP1"),
        fallback="", prompt_bucket=PROMPT_BUCKET, gen_bucket=GEN,
    )
    check_session(session)
    engine = session.engine(params, max_batch=N_REQUESTS)
    del params
    ops.reset_dispatch_counts()
    requests = make_requests(cfg, seed)
    done, secs = serve(engine, requests)
    n_tok = check_completions(done, N_REQUESTS, GEN, cfg.vocab_size)
    check_stats(engine.stats)
    st = engine.stats
    print(f"served {len(done)} requests, {n_tok} tokens in {secs:.1f} s "
          f"(cold: includes compilation); {st.decode_steps} decode steps, "
          f"{st.prefill_chunks} prefill chunks, {st.joins} joins")

    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(1, cfg.vocab_size, PROMPT_BUCKET).tolist()
    follow = rng.integers(1, cfg.vocab_size, CHECK_STEPS).tolist()
    t0 = time.perf_counter()
    got = engine_logits(engine, prompt, follow)
    want = reference_at(engine.params, cfg, prompt, follow, got)
    print(f"logit check ran in {time.perf_counter() - t0:.1f} s")
    check_logits("engine vs float32 reference, rel L2", got, want)
    print(f"dispatch: {dict(ops.DISPATCH_COUNTS)}")
    check_dispatch(ops.DISPATCH_COUNTS, ("decode", "flash", "gmm"))
    print(f"peak bytes in use: {peak_bytes([device])}")


def four_chips(seed: int, devices) -> None:
    import gc
    import threading

    import jax
    import numpy as np
    from jax.sharding import AxisType

    from repro.core import HAPSession, Workload, fixed_plan
    from repro.core.hardware import get_chip
    from repro.core.latency import LatencyModel
    from repro.kernels import ops
    from repro.launch.runtime import planner_chip
    from repro.models import count_params, init_params, param_shardings

    t0 = time.perf_counter()
    chip = planner_chip(devices[0])
    # the planner's latency model is fitted here from the seed (a few CPU
    # minutes), on a daemon thread so that it overlaps the TP4 leg and a
    # failed run does not wait for it
    fitted = {}

    def fit():
        fitted["model"] = LatencyModel(get_chip(chip), seed=seed)
        fitted["seconds"] = time.perf_counter() - t0

    fitter = threading.Thread(target=fit, daemon=True)
    fitter.start()
    cfg = smoke_config(None)
    mesh = jax.make_mesh((1, 4), ("data", "model"), devices=devices[:4],
                         axis_types=(AxisType.Auto,) * 2)
    workload = Workload(batch=N_REQUESTS, prompt=PROMPT_BUCKET, gen=GEN)
    requests = make_requests(cfg, seed)
    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(1, cfg.vocab_size, PROMPT_BUCKET).tolist()
    follow = rng.integers(1, cfg.vocab_size, CHECK_STEPS).tolist()
    logits, tokens = {}, {}
    for leg in ("tp4", "ilp"):
        if leg == "tp4":
            plan = fixed_plan("TP4", "TP4")
        else:
            fitter.join()
            if "model" not in fitted:
                raise SmokeFailure("fitting the latency model failed")
            print(f"{chip} latency model fitted from seed {seed} in "
                  f"{fitted['seconds']:.0f} s")
            planner = HAPSession(cfg, chip, 4, model=fitted["model"],
                                 mesh=mesh, fallback="",
                                 prompt_bucket=PROMPT_BUCKET, gen_bucket=GEN)
            check_session(planner)
            plan = planner.plan_for(workload)
            if planner.fallbacks:
                raise SmokeFailure("the ILP solve fell back")
        print(f"{leg}: {plan.describe()}")
        # the plan the ILP picked for this workload is pinned, so that
        # joins one by one do not re-plan for batches 1..7 mid-stream
        session = HAPSession(cfg, chip, 4, source=plan, mesh=mesh,
                             fallback="", prompt_bucket=PROMPT_BUCKET,
                             gen_bucket=GEN)
        check_session(session)
        layout = plan.to_sharding_plan(mesh, cfg, phase="decode")
        params = init_params(cfg, jax.random.PRNGKey(seed),
                             shardings=param_shardings(cfg, layout))
        check_expert_spans(params, 4)
        if leg == "tp4":
            n_params = count_params(params)
            print(f"config: {ARCH} at its widths, {cfg.dtype}, all "
                  f"{cfg.num_layers} layers; {n_params} params over 4 chips")
        engine = session.engine(params, max_batch=N_REQUESTS,
                                use_int4_transition=False)
        del params
        ops.reset_dispatch_counts()
        done, secs = serve(engine, requests)
        n_tok = check_completions(done, N_REQUESTS, GEN, cfg.vocab_size)
        check_stats(engine.stats)
        print(f"{leg}: served {len(done)} requests, {n_tok} tokens in "
              f"{secs:.1f} s (cold: includes compilation); "
              f"{time.perf_counter() - t0:.0f} s into the phase")
        logits[leg] = engine_logits(engine, prompt, follow)
        tokens[leg] = [c.tokens for c in done]
        print(f"{leg} dispatch: {dict(ops.DISPATCH_COUNTS)}")
        check_dispatch(ops.DISPATCH_COUNTS, ("decode", "flash", "gmm"))
        peaks = peak_bytes(devices[:4])
        print(f"{leg} peak bytes in use per chip: {peaks} (the model is "
              f"{n_params * 2} bytes)")
        if any(p is not None and p >= n_params * 2 for p in peaks):
            raise SmokeFailure("one chip held as many bytes as the model")
        del engine, session
        gc.collect()  # the next leg's weights need this leg's memory
    same = sum(a == b for a, b in zip(tokens["ilp"], tokens["tp4"]))
    print(f"greedy outputs identical for {same}/{N_REQUESTS} requests")
    check_logits("ILP plan vs static TP4, rel L2", logits["ilp"],
                 logits["tp4"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the sharded 28-layer ILP-vs-TP4 phase only")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch.runtime import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script: {e}",
              file=sys.stderr)
        return 2
    use_compile_cache()
    import jax

    devices = jax.devices()
    try:
        check_device(devices, args.chips)
        print(f"device: {devices[0].device_kind} x {len(devices)}")
        if args.chips == 4:
            four_chips(args.seed, devices)
        else:
            one_chip(args.seed, devices[0])
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
