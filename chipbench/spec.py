"""What a cell is made of, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. Each lives in a file of
its own: ``chipbench/configs/<config>.json`` and
``chipbench/traffic/<traffic>.json``; a per-layer metric is read by
``chipbench/metrics/<metric>.py``. Adding a cell, a configuration, a
mix or a metric is adding files: nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
from pathlib import Path
from typing import Any, Callable, Dict, List

HERE = Path(__file__).resolve().parent


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]  # chipbench/configs/<config>.json
    traffic: Dict[str, Any]  # chipbench/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]  # metrics this cell reports, trace 0
    per_layer: List[Dict[str, Any]]  # metrics this cell reports, trace 1
    root: Path

    def reader(self, metric: str) -> Callable:
        """The ``read`` function of ``chipbench/metrics/<metric>.py``."""
        path = self.root / "chipbench" / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise SpecError(f"no reader for per-layer metric {metric!r}: {path}")
        spec = importlib.util.spec_from_file_location(f"_metric_{metric}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _load_json(path: Path) -> Dict[str, Any]:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def _reported_in(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config {w['config']!r}")
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reported_in(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reported_in(m, name)],
        root=root,
    )


def round_up(x: int, q: int) -> int:
    return q * math.ceil(max(int(x), 0) / q)


def padded_prompt(n: int, bucket: int) -> int:
    """The engine's padded prompt length: its own bucket, at least one."""
    return round_up(max(n, 1), bucket)


def table_width(prompt: int, gen: int, bucket: int) -> int:
    """Block-table width (tokens) a live batch gets when this request is
    the largest queued: padded prompt + output budget + 1, bucketed."""
    return round_up(padded_prompt(prompt, bucket) + max(gen, 1) + 1, bucket)


def widths(traffic: Dict[str, Any], bucket: int) -> List[int]:
    """Every table width the mix can produce, smallest first."""
    p, g = traffic["prompt"], traffic["output"]
    lo = table_width(p["min"], g["min"], bucket)
    hi = table_width(p["max"], g["max"], bucket)
    return list(range(lo, hi + 1, bucket))


def kv_bytes_per_token(m: Dict[str, Any]) -> int:
    item = 2 if m["dtype"] == "bfloat16" else 4
    return m["num_layers"] * 2 * m["num_kv_heads"] * m["head_dim"] * item


def pool_blocks(config: Dict[str, Any], traffic: Dict[str, Any]) -> int:
    """KV pool blocks: every slot at the widest table, or what the
    config's ``kv_pool_bytes`` holds, whichever is fewer. Admission
    reserves each request's own need, so a pool under the worst case
    only makes a request wait for blocks when the running ones hold more
    than the pool."""
    eng = config["engine"]
    bs = eng["kv_block_size"]
    worst = traffic["slots"] * widths(traffic, eng["prompt_bucket"])[-1] // bs
    budget = eng["kv_pool_bytes"] // (kv_bytes_per_token(config["model"]) * bs)
    return min(worst, budget)
