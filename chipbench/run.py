"""Run one cell of the benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``chipbench/configs/<name>.json``: the model, the engine's settings, the
plan) and a traffic mix (``chipbench/traffic/<name>.json``). The run:

1. set-up: makes the weights on the chip from the seed, builds the
   engine through ``HAPSession(...).engine(...)``, warms every table
   width the mix can produce (chunk, fused and decode programs) with a
   few synthetic requests, and, for an offline mix, queues every request
   and serves until the first wave of slots has its prompts in the cache;
2. the window: drives the engine for ``--seconds`` (``serving_loop.Loop``),
   with the profiler on for a few seconds in its middle under
   ``--trace 1``;
3. after the window: reads the device's peak memory, cancels what is
   still queued or live (a live request keeps the tokens it was served),
   frees the program's state, and checks a sample of the served
   requests against the float32 reference (``check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its
limit, which also end standard error. A host where JAX finds no TPU, or
fewer chips than the cell asks for, or a checkout without the program
beside the benchmark, exits non-zero without that line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_SECONDS = 3.0  # profiler on this long in the middle of a traced window

sys.path.insert(0, str(HERE))

import spec  # noqa: E402


class BenchError(Exception):
    """The run cannot produce a result: exit non-zero without a line."""


# -- compilations, counted by JAX's own monitoring events --------------------
_LOWERINGS = [0]


def _count_lowerings() -> None:
    import jax

    def listen(event, duration=None, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            _LOWERINGS[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)


def log(msg: str) -> None:
    print(msg, flush=True)


# -- the program, through its serving entry -----------------------------------
def program_config(config: Dict[str, Any]):
    """The registry's config with the file's ``model`` section applied."""
    from repro.configs import get_config

    base = get_config(config["registry"])
    names = {f.name for f in dataclasses.fields(base)}
    unknown = sorted(set(config["model"]) - names)
    if unknown:
        raise BenchError(f"config keys the program does not have: {unknown}")
    return dataclasses.replace(base, **config["model"])


def check_layout(params, cfg) -> None:
    import jax

    from repro.models import param_shapes

    want = jax.tree.leaves_with_path(param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    got = jax.tree.leaves_with_path(params)
    if [(p, tuple(s)) for p, s in want] != [(p, tuple(a.shape)) for p, a in got]:
        raise BenchError("the benchmark's weight layout is not the engine's")


def build_engine(cell: spec.Cell, params):
    from repro.core import HAPSession, fixed_plan

    cfg = program_config(cell.config)
    eng, plan = cell.config["engine"], cell.config["plan"]
    check_layout(params, cfg)
    session = HAPSession(
        cfg, plan["chip"], cell.chips,
        source=fixed_plan(plan["attn"], plan["experts"]),
        fallback="", prompt_bucket=eng["prompt_bucket"], gen_bucket=eng["gen_bucket"])
    pool = spec.pool_blocks(cell.config, cell.traffic)
    return session.engine(params, max_batch=cell.traffic["slots"], kv_block_size=eng["kv_block_size"],
                          kv_blocks=pool), pool


def warm(loop, width: int, bucket: int, max_prompt: int, vocab: int) -> None:
    """Run the chunk, fused and decode programs at table width ``width``
    with two synthetic requests: A (one chunk) decodes while B's prompt
    (as many chunks as the mix's longest prompt has, its need exactly
    ``width``) goes through; then both are cancelled. The live batch is
    left in place, empty."""
    import numpy as np

    from repro.serving import Request

    e, ad = loop.engine, loop.ad
    if ad.live():
        ad.drop()
    rng = np.random.default_rng(width)
    pb = min(width - bucket, spec.padded_prompt(max_prompt, bucket))
    a = e.submit(Request(prompt=rng.integers(1, vocab, bucket // 2).tolist(),
                         max_new_tokens=4))
    b = e.submit(Request(prompt=rng.integers(1, vocab, pb).tolist(),
                         max_new_tokens=width - pb - 1))
    loop.rec.submitted(a, 0.0, np.zeros(bucket // 2), 4)
    loop.rec.submitted(b, 0.0, np.zeros(pb), width - pb - 1)
    after = 0
    while after < 2:
        loop.iterate(math.inf)
        done_b = loop.rec.reqs[b].token_times
        if done_b and loop.rec.steps and loop.rec.steps[-1].step.kind == "decode":
            after += 1
    if ad.width() != width:
        raise BenchError(f"warm-up sized a table {ad.width()} wide, not {width}")
    e.cancel(a)
    e.cancel(b)
    ad.reap()
    loop.rec.retired(e.retire())


# -- one run --------------------------------------------------------------------
@dataclasses.dataclass
class Window:
    rec: Any  # serving_loop.Recorder of what was served from the window's set-up on
    t0: float  # the window opens (perf_counter seconds)
    t1: float  # the last step of the window has returned
    end: float  # requests due before this were submitted
    lowered: int  # programs lowered (compiled or fetched) inside the window
    counters: Dict[str, int]  # the engine's stats when ``rec`` began recording
    traced: tuple = (0.0, 0.0)  # perf_counter span the profiler was on
    mark: float = 0.0  # perf_counter time of the ``bench.mark`` span
    trace_dir: Optional[str] = None


def prepare(cell: spec.Cell, params):
    """The engine, warmed at every table width the mix uses, its live
    batch left in place at the widest; and the warm-up loop.

    The widest batch is the steady state of a server under sustained
    load: a batch sized to the queue widens at each head-of-line drain
    (a queued request that needs a wider table than the batch has), and
    once it is as wide as any request of the mix needs it drains no more
    while rows stay live. The window measures that state, not the ramp of
    a server just started."""
    import jax

    import serving_loop
    from repro.kernels import ops
    from repro.serving import SamplingParams

    eng, tmix = cell.config["engine"], cell.traffic
    bucket = eng["prompt_bucket"]
    engine, pool = build_engine(cell, params)
    ops.reset_dispatch_counts()
    widths = spec.widths(tmix, bucket)
    widths = widths if tmix["loop"] == "open" else widths[-1:]
    key = jax.random.PRNGKey(0)  # greedy: the key is never drawn from
    wloop = serving_loop.Loop(engine, serving_loop.Recorder(bucket), SamplingParams(),
                              key)
    took = []
    for w in widths:
        t = time.perf_counter()
        warm(wloop, w, bucket, tmix["prompt"]["max"], cell.config["model"]["vocab_size"])
        took.append(round(time.perf_counter() - t, 3))
    log(f"warmed table widths {widths} (tokens) in {took} s, {tmix['slots']} slots, "
        f"pool {pool} blocks of {eng['kv_block_size']}; {len(wloop.rec.steps)} warm-up steps")
    return engine, wloop


def serve(cell: spec.Cell, engine, key, seed: int, seconds: float, trace: bool,
          t_start: float) -> Window:
    """Queue the seed's traffic (an offline mix: all of it, served until
    the first wave of slots is prefilled), then drive the engine for
    ``seconds``; under ``trace`` the profiler is on for
    ``TRACE_SECONDS`` in the middle."""
    import jax

    import serving_loop
    import traffic as tg
    from repro.serving import Request, SamplingParams

    tmix, bucket = cell.traffic, cell.config["engine"]["prompt_bucket"]
    reqs = [(r.due, Request(prompt=r.prompt.tolist(), max_new_tokens=r.max_new))
            for r in tg.generate(tmix, seconds, seed, cell.config["model"]["vocab_size"])]
    rec = serving_loop.Recorder(bucket)
    counters = dataclasses.asdict(engine.stats)
    loop = serving_loop.Loop(engine, rec, SamplingParams(), key)
    if tmix["loop"] == "offline":
        now = time.perf_counter()
        loop.pending = [(now, q) for _, q in reqs]
        loop.submit_due(now)
        first_wave = sorted(rec.reqs)[: tmix["slots"]]
        while not all(rec.reqs[u].token_times for u in first_wave):
            loop.iterate(math.inf)
        log(f"offline: {len(reqs)} requests queued; {len(rec.steps)} steps before the "
            f"window (first wave) in {time.perf_counter() - now:.3f} s")
    lowered0 = _LOWERINGS[0]
    t0 = time.perf_counter()
    win = Window(rec, t0, t0, t0 + seconds, 0, counters)
    log(f"set-up {t0 - t_start:.3f} s")
    if tmix["loop"] == "open":
        loop.pending = [(t0 + due, q) for due, q in reqs]
    t_on = t0 + max(0.0, (seconds - TRACE_SECONDS) / 2) if trace else math.inf
    if trace:
        win.trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
    while time.perf_counter() < win.end:
        now = time.perf_counter()
        if now >= t_on and not win.mark:
            jax.profiler.start_trace(win.trace_dir)
            with jax.profiler.TraceAnnotation("bench.mark"):
                win.mark = time.perf_counter()
            rec.tracing = True
        elif rec.tracing and now >= win.mark + TRACE_SECONDS:
            _stop_trace(win)
        loop.iterate(win.end)
    if rec.tracing:
        _stop_trace(win)
    win.t1 = time.perf_counter()
    win.lowered = _LOWERINGS[0] - lowered0
    return win


def _stop_trace(win: Window) -> None:
    import jax

    win.rec.tracing = False
    win.traced = (win.mark, time.perf_counter())
    jax.profiler.stop_trace()


def clear(engine, rec) -> None:
    """Cancel whatever is queued or live and drop the live batch; a live
    request retires with the tokens it was served."""
    from adapter import Adapter

    ad = Adapter(engine)
    for r in rec.reqs.values():
        if r.status is None:
            engine.cancel(r.uid)
    ad.reap()
    rec.retired(engine.retire())
    ad.drop()


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, device,
             peaks: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    from jax.sharding import SingleDeviceSharding

    import check
    import devtrace
    import readings as rd
    import weights
    from repro.kernels import ops

    m, tmix = cell.config["model"], cell.traffic
    sharding = SingleDeviceSharding(device)
    params = weights.make(m, seed, sharding)
    jax.block_until_ready(params)
    log(f"weights made {time.perf_counter() - T_START:.3f} s after start")
    engine, wloop = prepare(cell, params)
    del params
    win = serve(cell, engine, wloop.key, seed, seconds, trace, T_START)
    rec, t0, t1, lowered = win.rec, win.t0, win.t1, win.lowered
    setup_s = t0 - T_START
    stats = dataclasses.asdict(engine.stats)
    dispatch = dict(ops.DISPATCH_COUNTS)
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)

    run = rd.Run(model=m, traffic=tmix, peaks=peaks, rec=rec, window=(t0, t1),
                 due_until=win.end)
    if trace:
        tr = devtrace.load(devtrace.find_xplane(win.trace_dir))
        shutil.rmtree(win.trace_dir, ignore_errors=True)
        marks = [s for s in tr.spans if s.name == "bench.mark"]
        if not marks or not tr.ops:
            raise BenchError("the trace holds no device operations or no mark")
        run.trace, run.offset, run.traced = tr, marks[0].start - win.mark, win.traced

    # -- counts and medians on earlier lines ------------------------------
    kinds: Dict[str, int] = {}
    for s in run.steps():
        kinds[s.step.kind] = kinds.get(s.step.kind, 0) + 1
    ttft, tbt = rd.ttfts(run), rd.tbts(run)
    done = [r for r in rec.reqs.values() if r.status is not None]
    log(f"window {t1 - t0:.3f} s after {setup_s:.3f} s of set-up; steps {kinds}; "
        f"requests due in window {len(rd.due_in_window(run))}, finished {len(done)}; "
        f"output tokens {rd.output_tokens(run)}, prompt tokens {rd.prompt_tokens(run)}; "
        f"queued at the close {len(engine.scheduler)}")
    log(f"ttft samples {len(ttft)} median_ms {fmt(rd.median(ttft), 1e3)} "
        f"p95_ms {fmt(rd.pctl(ttft, 95), 1e3)}; tbt samples {len(tbt)} median_ms "
        f"{fmt(rd.median(tbt), 1e3)} p95_ms {fmt(rd.pctl(tbt, 95), 1e3)}")
    if tmix["loop"] == "open":
        late = [r for r in rd.due_in_window(run) if not r.token_times]
        lag = max((s.start - r.due for r in rd.due_in_window(run)
                   for s in rec.steps[:1]), default=0.0)
        log(f"open loop: {len(late)} requests due in the window had no first token "
            f"by its close; first step began {lag * 1e3:.1f} ms after the first due time")
    log(f"engine stats: { {k: v for k, v in stats.items() if v} }")
    log(f"dispatch: {dispatch}")
    log(f"compilations inside the window: {lowered}")
    log(f"peak bytes in use: {peak}")

    metrics: Dict[str, Any] = {}
    out: Dict[str, Any] = {}
    if trace:
        lo, hi = win.traced[0] + run.offset, win.traced[1] + run.offset
        busy_s = devtrace.busy(run.trace, lo, hi)
        out["breakdown"] = {"device_ops": devtrace.top_ops(run.trace, lo, hi),
                            "idle_gaps": devtrace.top_gaps(run.trace, lo, hi)}
        log(f"traced {hi - lo:.3f} s, device busy {busy_s:.4f} s; idle by host span: "
            f"{devtrace.idle_by_span(run.trace, lo, hi)}")
        for mdef in cell.per_layer:
            v = cell.reader(mdef["name"])(run)
            if v is not None:
                metrics[mdef["name"]] = {"value": v, "unit": mdef["unit"]}
    else:
        values = {"ttft_p95_ms": rd.ms(rd.pctl(ttft, 95)),
                  "tbt_p95_ms": rd.ms(rd.pctl(tbt, 95)),
                  "output_tok_s": rd.output_tokens(run) / (t1 - t0),
                  "setup_s": setup_s}
        for mdef in cell.end_to_end:
            if values.get(mdef["name"]) is not None:
                metrics[mdef["name"]] = {"value": values[mdef["name"]],
                                         "unit": mdef["unit"]}

    # -- cut what is left, free the program, then the reference ----------------
    failed = [r.uid for r in done if r.status != "ok" or len(r.tokens) != r.max_new
              or not all(0 <= t < m["vocab_size"] for t in r.tokens)]
    drift = check.step_kind_drift(rec.steps, win.counters, stats)
    clear(engine, rec)
    del win, wloop, engine, run
    gc.collect()
    limits = json.loads((cell.root / "chipbench" / "limits" / f"{cell.name}.json").read_text())
    picked = check.sample(rec.reqs.values(), seed, limits["sample_tokens"],
                          limits["sample_most"])
    t_ref = time.perf_counter()
    params = weights.make(m, seed, sharding)
    stats = check.statistics([check.gaps(params, m, r)["served"] for r in picked])
    log(f"reference over {len(picked)} requests ({sum(len(r.tokens) for r in picked)} "
        f"served tokens, longest {picked[0].padded + len(picked[0].tokens) if picked else 0}"
        f" positions) in {time.perf_counter() - t_ref:.1f} s; served-token gaps {stats}")
    quiet = sorted(k for k, n in dispatch.items() if n and ".ref" in k)
    kernels = [f for f in ("decode", "gmm")
               if not any(k.startswith(f + ".pallas") and n for k, n in dispatch.items())]
    checks = {
        check.COMPARED: [stats[check.COMPARED], limits["limit"]],
        "failed_requests": [len(failed), 0],
        "step_kind_drift": [drift, 0],
        "window_compilations": [lowered, 0],
        "reference_kernel_branches": [len(quiet) + len(kernels), 0],
    }
    correct = all(check.passes(v, lim) for v, lim in checks.values()) and bool(picked)
    out.update(
        correct=correct,
        attempted=len(rec.reqs),
        failed=len(failed),
        metrics=metrics,
        device={"platform": device.platform, "kind": device.device_kind,
                "count": len(jax.devices()), "memory_peak_bytes": peak},
    )
    if trace:
        out["device"].update(busy_s=busy_s, window_s=hi - lo)
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return out


def fmt(x: Optional[float], scale: float = 1.0) -> str:
    return "none" if x is None else f"{x * scale:.3f}"


def find_peaks(kind: str) -> Dict[str, Any]:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"no published peaks for device kind {kind!r} "
                         f"(known: {sorted(table)})")
    return table[kind]


def main(argv=None, require_tpu: bool = True, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(root, args.workload)
        if not (root / "src" / "repro").is_dir():
            raise BenchError(f"the program is not beside the benchmark: no "
                             f"{root / 'src' / 'repro'}")
        sys.path.insert(0, str(root / "src"))
        import jax

        cache = root / ".chipbench_cache" / "jax"  # fixed: the path keys the cache
        cache.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(cache))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _count_lowerings()
        devices = jax.devices()
        if require_tpu:
            if devices[0].platform != "tpu":
                raise BenchError(f"no TPU: JAX reports {devices[0].platform}")
            if len(devices) < cell.chips:
                raise BenchError(f"{cell.chips} chips asked for, JAX sees {len(devices)}")
            peaks = find_peaks(devices[0].device_kind)
        else:
            peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
        log(f"device: {devices[0].platform} {devices[0].device_kind} x {len(devices)}, "
            f"{time.perf_counter() - T_START:.3f} s after start")
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices[0], peaks)
    except (BenchError, spec.SpecError, ImportError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
