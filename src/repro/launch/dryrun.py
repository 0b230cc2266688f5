import os
# a dry run compiles for 512 virtual CPU devices and never takes an
# accelerator: pin the CPU, and add the device count to any XLA flags
# already set instead of replacing them
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    f for f in (os.environ.get("XLA_FLAGS", ""),
                "--xla_force_host_platform_device_count=512") if f)

"""Multi-pod dry run: prove the distribution config is coherent.

For every (architecture x input shape), lower + compile the relevant step
function (train_step / prefill / serve decode_step) on the production mesh
— 16x16 single pod and 2x16x16 multi-pod — with ShapeDtypeStruct inputs
(no allocation), then print ``memory_analysis()`` (fits) and
``cost_analysis()`` (FLOPs/bytes for the roofline table).

Roofline numbers are scan-corrected via per-layer probe compiles (see
launch/roofline.py): XLA counts a lax.scan body once, so we compile
1-layer and 2-layer variants, scanned and unrolled, and combine.

NOTE the environment lines above MUST precede any jax import: jax locks
the platform and device count at first init. They are set here and ONLY
here.

Usage:
  python -m repro.launch.dryrun --arch mistral-nemo-12b --shape train_4k
  python -m repro.launch.dryrun --all --both-meshes --out runs.jsonl
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.models import input_specs, supported_shapes
from repro.models.io import INPUT_SHAPES
from repro.models.params import abstract_params, param_pspecs
from repro.models.transformer import scan_unroll
from repro.sharding.specs import adapt_plan_for_batch, make_plan
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _batch_pspecs(cfg, batch_specs, plan) -> Dict[str, Any]:
    dp = plan.dp
    return {k: P(dp, *([None] * (len(v.shape) - 1)))
            for k, v in batch_specs.items()}


def _opt_specs(pspecs):
    from repro.training.optimizer import AdamWState
    return AdamWState(step=P(), mu=pspecs, nu=pspecs)


def _abstract_opt(aparams):
    from repro.training.optimizer import AdamWState
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)  # noqa: E731
    return AdamWState(step=jax.ShapeDtypeStruct((), jnp.int32),
                      mu=jax.tree.map(f32, aparams),
                      nu=jax.tree.map(f32, aparams))


def build_lowerable(cfg, shape_name: str, mesh, plan
                    ) -> Tuple[Any, Any, Tuple]:
    """(fn, in_shardings, abstract_args) for one combination."""
    seq, batch, kind = INPUT_SHAPES[shape_name]
    aparams = abstract_params(cfg)
    pspecs = param_pspecs(cfg, plan)
    specs = input_specs(cfg, shape_name)

    if kind == "train":
        from repro.training.train_loop import TrainState, make_train_step
        fn = make_train_step(cfg, plan, remat=True)
        state = TrainState(params=aparams, opt=_abstract_opt(aparams))
        state_specs = TrainState(params=pspecs, opt=_opt_specs(pspecs))
        bspecs = _batch_pspecs(cfg, specs["batch"], plan)
        return fn, (_named(mesh, state_specs), _named(mesh, bspecs)), \
            (state, specs["batch"])
    if kind == "prefill":
        bspecs = _batch_pspecs(cfg, specs["batch"], plan)
        if cfg.is_encoder_only:
            # encoder-only (hubert): "prefill" is the full encoder forward
            from repro.models.transformer import (embed_inputs,
                                                  forward_hidden, unembed)

            def fn(params, batch):
                x = embed_inputs(params, cfg, batch, plan)
                h, _, _ = forward_hidden(params, cfg, x, plan)
                return unembed(params, cfg, h)
        else:
            from repro.models import prefill

            def fn(params, batch):
                return prefill(params, cfg, batch, max_len=seq, plan=plan)
        return fn, (_named(mesh, pspecs), _named(mesh, bspecs)), \
            (aparams, specs["batch"])

    from repro.models import decode_step
    from repro.models.transformer import DecodeCache

    def fn(params, token, cache):
        return decode_step(params, cfg, token, cache, plan=plan)
    cache_specs = DecodeCache(
        k=plan.kv_cache_spec() if cfg.has_attention else None,
        v=plan.kv_cache_spec() if cfg.has_attention else None,
        conv=plan.conv_cache_spec() if cfg.has_mamba else None,
        ssm=plan.ssm_cache_spec() if cfg.has_mamba else None,
        pos=P())
    tok_sh = NamedSharding(mesh, P(plan.dp, None))
    cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), cache_specs,
                            is_leaf=lambda x: isinstance(x, P))
    return fn, (_named(mesh, pspecs), tok_sh, cache_sh), \
        (aparams, specs["token"], specs["cache"])


def _compile(cfg, shape_name, mesh, plan, unroll: int = 1):
    fn, in_sh, args = build_lowerable(cfg, shape_name, mesh, plan)
    with scan_unroll(unroll):
        jitted = jax.jit(fn, in_shardings=in_sh)
        with mesh:
            lowered = jitted.lower(*args)
            return lowered.compile()


def probe_layer_costs(cfg, shape_name: str, mesh, plan) -> roofline.Costs:
    """Per-layer cost: compile the scan BODY standalone (see probes.py)."""
    from repro.launch.probes import probe_layer_costs as _probe
    return _probe(cfg, shape_name, mesh, plan)


def _session_plan(cfg, mesh, seq: int, batch: int, kind: str,
                  source: str, chip: str):
    """Strategy via a PlanSource (ILP planner or static baselines), bridged
    onto the mesh with ``HAPPlan.to_sharding_plan`` — the adaptive path."""
    from repro.core import HAPSession, Workload
    from repro.core.latency import cached_latency_model
    session = HAPSession(cfg, chip, mesh.size, source=source,
                         model=cached_latency_model(chip), mesh=mesh,
                         prompt_bucket=max(seq, 1))
    w = Workload(batch=batch, prompt=seq, gen=64)
    phase = "decode" if kind == "decode" else "prefill"
    return session.sharding_plan(w, phase=phase)


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              expert_mode: str = "", attn_mode: str = "", kv_shard: str = "",
              probe: bool = True, verbose: bool = True,
              cfg_override=None, plan_override=None,
              source: str = "baseline", chip: str = "a6000"
              ) -> Optional[roofline.RooflineReport]:
    cfg = cfg_override or get_config(arch)
    status = supported_shapes(cfg)[shape_name]
    if status != "ok":
        if verbose:
            print(f"{arch} x {shape_name}: {status}", flush=True)
        return None

    seq, batch, kind = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    if plan_override is not None:
        plan = plan_override
    elif source != "baseline" and kind != "train":
        # HAP is an inference planner; training shapes keep the baseline.
        plan = _session_plan(cfg, mesh, seq, batch, kind, source, chip)
        plan = adapt_plan_for_batch(plan, cfg, batch, kind)
    else:
        plan = make_plan(mesh, cfg, expert_mode=expert_mode,
                         attn_override=attn_mode, kv_shard=kv_shard)
        plan = adapt_plan_for_batch(plan, cfg, batch, kind)

    t0 = time.time()
    compiled = _compile(cfg, shape_name, mesh, plan)
    t_compile = time.time() - t0
    full = roofline.extract_costs(compiled)
    peak = roofline.peak_memory(compiled)

    body = None
    if probe:
        t1 = time.time()
        body = probe_layer_costs(cfg, shape_name, mesh, plan)
        if verbose:
            print(f"  probes: {time.time()-t1:.1f}s", flush=True)

    mesh_name = "2x16x16" if multi_pod else "16x16"
    rep = roofline.build_report(
        arch=arch, shape=shape_name, mesh_name=mesh_name,
        n_devices=mesh.size, cfg=cfg, full=full, layer_body=body,
        peak_mem=peak)
    if verbose:
        mem = compiled.memory_analysis()
        print(f"{arch} x {shape_name} [{mesh_name}] compile={t_compile:.1f}s "
              f"plan=(attn={plan.attn_mode},kv={plan.kv_shard},"
              f"ffn={plan.ffn_mode},sp={plan.seq_shard_acts})", flush=True)
        print(f"  memory/device: args={mem.argument_size_in_bytes/2**30:.2f}"
              f"GiB temps={mem.temp_size_in_bytes/2**30:.2f}GiB "
              f"out={mem.output_size_in_bytes/2**30:.2f}GiB")
        print(f"  roofline: compute={rep.t_compute*1e3:.2f}ms "
              f"memory={rep.t_memory*1e3:.2f}ms "
              f"collective={rep.t_collective*1e3:.2f}ms "
              f"-> {rep.bottleneck}-bound "
              f"(useful-flops ratio {rep.flops_ratio:.3f})", flush=True)
        for kc, v in sorted(rep.coll_breakdown.items()):
            if v > 0:
                print(f"    {kc}: {v/2**20:.1f} MiB/device wire")
    return rep


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="", choices=[""] + list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-probe", action="store_true")
    ap.add_argument("--expert-mode", default="", choices=["", "ep", "tp"])
    ap.add_argument("--attn-mode", default="",
                    choices=["", "tp_heads", "replicated"])
    ap.add_argument("--kv-shard", default="",
                    choices=["", "heads", "seq", "seq_all"])
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-3 parameter sharding over all mesh axes "
                         "(EXPERIMENTS.md §Perf b)")
    ap.add_argument("--kv-dtype", default="",
                    help="KV cache dtype override, e.g. float8_e4m3fn "
                         "(§Perf a)")
    ap.add_argument("--source", default="baseline",
                    choices=["baseline", "ilp", "tp", "ep"],
                    help="strategy source for inference shapes: mesh "
                         "baseline, the HAP ILP planner, or static TP/EP "
                         "(bridged via HAPPlan.to_sharding_plan)")
    ap.add_argument("--chip", default="a6000",
                    help="hardware model for --source ilp planning")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.source != "baseline" and (args.expert_mode or args.attn_mode
                                      or args.kv_shard):
        ap.error("--expert-mode/--attn-mode/--kv-shard only apply to "
                 "--source baseline (the strategy source decides layouts)")

    archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    rows, failures = [], []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                # probe (roofline detail) only on the single-pod mesh;
                # the multi-pod pass proves the "pod" axis shards.
                do_probe = (not args.no_probe) and not mp
                cfg_override = None
                plan_override = None
                if args.kv_dtype:
                    cfg_override = dataclasses.replace(
                        get_config(arch), kv_cache_dtype=args.kv_dtype)
                if args.fsdp:
                    from repro.sharding.specs import ShardingPlan
                    mesh_ = make_production_mesh(multi_pod=mp)
                    plan_override = ShardingPlan(
                        mesh=mesh_, dp_axes=mesh_.axis_names,
                        attn_mode="replicated", kv_shard="none",
                        ffn_mode="tp", ffn_tp_axis=None, ep_axis=None,
                        fsdp=True)
                try:
                    rep = lower_one(
                        arch, shape, multi_pod=mp, probe=do_probe,
                        expert_mode=args.expert_mode,
                        attn_mode=args.attn_mode, kv_shard=args.kv_shard,
                        cfg_override=cfg_override,
                        plan_override=plan_override,
                        source=args.source, chip=args.chip)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape, mp))
                    print(f"FAIL {arch} x {shape} multi_pod={mp}: {e}")
                    traceback.print_exc()
                    continue
                if rep is None:
                    rows.append({"arch": arch, "shape": shape,
                                 "mesh": "2x16x16" if mp else "16x16",
                                 "status": "skip",
                                 "reason": supported_shapes(
                                     get_config(arch))[shape]})
                else:
                    rows.append({
                        "arch": arch, "shape": shape, "mesh": rep.mesh,
                        "status": "ok", "hlo_flops": rep.hlo_flops,
                        "hlo_bytes": rep.hlo_bytes,
                        "coll_bytes": rep.coll_bytes,
                        "coll_breakdown": rep.coll_breakdown,
                        "model_flops": rep.model_flops,
                        "t_compute": rep.t_compute,
                        "t_memory": rep.t_memory,
                        "t_collective": rep.t_collective,
                        "bottleneck": rep.bottleneck,
                        "flops_ratio": rep.flops_ratio,
                        "peak_mem_gib": rep.peak_mem_bytes / 2**30,
                    })
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rows[-1]) + "\n")
    print(f"\n{len([r for r in rows if r['status'] == 'ok'])} ok, "
          f"{len([r for r in rows if r['status'] == 'skip'])} skipped, "
          f"{len(failures)} failed")
    for f_ in failures:
        print("  FAIL:", *f_)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
