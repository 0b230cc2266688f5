"""Find the highest rate an open-loop cell sustains without a growing backlog.

    python chipbench/sweep.py --workload <cell> --seconds <s> --seed <n> --rates <r> [<r> ...]

In one process and one engine (built and warmed once), the cell's mix
is served at each rate in turn for ``--seconds``; between rates whatever
is left is cancelled and the live batch dropped. For each rate one JSON
line: requests due, those with no first token by the close, the queue at
the close, and the median time to first token in the first and the last
third of the window. A backlog grows where the last third waits far
longer than the first, or requests are left without a first token. The
cell's ``rate_per_s`` is then set at about four fifths of the highest
rate that sustains.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None, require_tpu: bool = True, root=run.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(root, args.workload)
    if cell.traffic["loop"] != "open":
        print("sweep: the cell's mix is not an open loop", file=sys.stderr)
        return 1
    sys.path.insert(0, str(root / "src"))
    import jax
    from jax.sharding import SingleDeviceSharding

    import readings as rd
    import weights

    device = jax.devices()[0]
    if require_tpu and device.platform != "tpu":
        print(f"sweep: no TPU: JAX reports {device.platform}", file=sys.stderr)
        return 1
    params = weights.make(cell.config["model"], args.seed, SingleDeviceSharding(device))
    engine, wloop = run.prepare(cell, params)
    del params
    for rate in args.rates:
        cell = dataclasses.replace(cell, traffic=dict(cell.traffic, rate_per_s=rate))
        win = run.serve(cell, engine, wloop.key, args.seed, args.seconds, False,
                        time.perf_counter())
        r = rd.Run(model=cell.config["model"], traffic=cell.traffic, peaks={},
                   rec=win.rec, window=(win.t0, win.t1), due_until=win.end)
        due = sorted(rd.due_in_window(r), key=lambda q: q.due)
        ttft = rd.ttfts(r)
        by_due = [t for _, t in sorted(zip([q.due for q in due], ttft))]
        third = max(1, len(by_due) // 3)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "no_first_token": sum(not q.token_times for q in due),
            "queued_at_close": len(engine.scheduler),
            "ttft_median_ms_first_third": rd.ms(rd.median(by_due[:third])),
            "ttft_median_ms_last_third": rd.ms(rd.median(by_due[-third:])),
            "ttft_p95_ms": rd.ms(rd.pctl(ttft, 95)),
            "tbt_p95_ms": rd.ms(rd.pctl(rd.tbts(r), 95)),
            "window_compilations": win.lowered}), flush=True)
        run.clear(engine, win.rec)
        run.warm(wloop, spec.widths(cell.traffic, cell.config["engine"]["prompt_bucket"])[-1],
                 cell.config["engine"]["prompt_bucket"], cell.traffic["prompt"]["max"],
                 cell.config["model"]["vocab_size"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
