"""The readings a cell's limit (``chipbench/limits/<cell>.json``) is set from.

    python chipbench/calibrate.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

In one process, for each seed: the weights for that seed go into one
engine (built and warmed once, at the cell's own sizes), the seed's
traffic is served for ``--seconds`` at the cell's own load through the
same window as ``run.py``, what is left is cut as ``run.py`` cuts it,
and the sample ``run.py`` would check is run through the float32
reference. Two numbers come out per seed for each statistic of
``check.STATISTICS``: the program's (its lower reading: the largest over
the seeds) and the control's, the reference computed with float8
operands (its upper reading: the smallest over the seeds). Each seed's
line also gives both verdicts under the cell's committed limit, by the
test ``run.py`` uses (``check.passes`` on ``check.COMPARED``): the
program's has to be true and the control's false. Then one summary line
per statistic; ``--dump`` keeps every sampled token's gaps.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None, require_tpu: bool = True, root=run.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dump", help="write every sampled token's gaps here (JSON)")
    args = ap.parse_args(argv)
    cell = spec.load_cell(root, args.workload)
    sys.path.insert(0, str(root / "src"))
    import jax
    from jax.sharding import SingleDeviceSharding

    import check
    import weights

    device = jax.devices()[0]
    if require_tpu and device.platform != "tpu":
        print(f"calibrate: no TPU: JAX reports {device.platform}", file=sys.stderr)
        return 1
    limits = json.loads((root / "chipbench" / "limits" / f"{cell.name}.json").read_text())
    m = cell.config["model"]
    sharding = SingleDeviceSharding(device)
    engine = wloop = None
    served, control, raw = [], [], []
    for seed in args.seeds:
        t = time.perf_counter()
        if engine is not None:
            engine.params = None
            gc.collect()
        params = weights.make(m, seed, sharding)
        if engine is None:
            engine, wloop = run.prepare(cell, params)
        else:
            engine.params = params
            run.warm(wloop, spec.widths(cell.traffic, cell.config["engine"]["prompt_bucket"])[-1],
                     cell.config["engine"]["prompt_bucket"], cell.traffic["prompt"]["max"],
                     m["vocab_size"])
        del params
        win = run.serve(cell, engine, wloop.key, seed, args.seconds, False, t)
        run.clear(engine, win.rec)
        gc.collect()
        picked = check.sample(win.rec.reqs.values(), seed, limits["sample_tokens"],
                              limits["sample_most"])
        g = [check.gaps(engine.params, m, r, control=True) for r in picked]
        s = check.statistics([x["served"] for x in g])
        c = check.statistics([x["control"] for x in g])
        served.append(s)
        control.append(c)
        print(json.dumps({"seed": seed, "requests": len(picked),
                          "tokens": sum(len(r.tokens) for r in picked),
                          "served": s, "control": c, "limit": limits["limit"],
                          "correct": check.passes(s[check.COMPARED], limits["limit"]),
                          "control_correct": check.passes(c[check.COMPARED], limits["limit"]),
                          "window_compilations": win.lowered}), flush=True)
        if args.dump:
            raw.append({"seed": seed, "requests": [
                {"uid": r.uid, "prompt": len(r.prompt), "padded": r.padded,
                 **{k: v.tolist() for k, v in x.items()}} for r, x in zip(picked, g)]})
    if args.dump:
        Path(args.dump).write_text(json.dumps(raw))
    for k in check.STATISTICS:
        lower = max(s[k] for s in served)
        upper = min(c[k] for c in control)
        print(json.dumps({"workload": cell.name, "seeds": len(args.seeds), "statistic": k,
                          "lower": lower, "upper": upper,
                          "ratio": upper / lower if lower else None}))
    k = check.COMPARED
    print(json.dumps({"workload": cell.name, "compared": k, "limit": limits["limit"],
                      "program_correct_seeds": sum(check.passes(s[k], limits["limit"])
                                                   for s in served),
                      "control_correct_seeds": sum(check.passes(c[k], limits["limit"])
                                                   for c in control)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
