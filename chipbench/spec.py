"""What a cell is made of, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. Each lives in a file of
its own: ``chipbench/configs/<config>.json`` and
``chipbench/traffic/<traffic>.json``; a per-layer metric is read by
``chipbench/metrics/<metric>.py``. A configuration names its
architecture with a top-level ``"arch"``, beside ``registry``: the
module ``chipbench/arch/<arch>.py``, which gives what differs by
architecture and nothing else:

- ``shapes(m)``: the leaf-shape tree, in the program's parameter order;
- ``ONES``: the names of the leaves drawn as ones (norm scales);
- ``layers(m, params, x, fp8)``: the float32 layer stack of the
  reference, hidden states in, hidden states out, every layer;
- ``kv_bytes(m)``: KV cache bytes of one token in one layer;
- ``attn_proj_flops(m)``: one layer's attention projections, per token;
- ``attn_core_flops(m)``: one layer's attention per attended position;
- ``attn_cache_bytes(m)``: cache bytes read per attended position;
- ``KERNELS``: the kernel families whose Pallas branch a correct run
  must have taken.

``m`` is a config file's ``model`` section. Adding a cell, a
configuration, an architecture, a mix or a metric is adding files:
nothing here names one.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
from pathlib import Path
from typing import Any, Callable, Dict, List

HERE = Path(__file__).resolve().parent
# the architecture a caller gets that passes a model section alone
DEFAULT_ARCH = "moe_gqa"


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]  # chipbench/configs/<config>.json
    arch: Any  # chipbench/arch/<config's arch>.py, the module
    traffic: Dict[str, Any]  # chipbench/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]  # metrics this cell reports, trace 0
    per_layer: List[Dict[str, Any]]  # metrics this cell reports, trace 1
    root: Path

    def reader(self, metric: str) -> Callable:
        """The ``read`` function of ``chipbench/metrics/<metric>.py``."""
        path = self.root / "chipbench" / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise SpecError(f"no reader for per-layer metric {metric!r}: {path}")
        return _module(str(path), f"_metric_{metric}").read


@functools.lru_cache(maxsize=None)
def _module(path: str, name: str):
    """The module at ``path``, executed once per process."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_arch(root: Path, name: str):
    """The architecture module ``<root>/chipbench/arch/<name>.py``."""
    path = root / "chipbench" / "arch" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no architecture module {name!r}: {path}")
    return _module(str(path.resolve()), f"_arch_{name}")


def resolve(arch=None):
    """``arch``, or for a caller that names none ``DEFAULT_ARCH``."""
    return load_arch(HERE.parent, DEFAULT_ARCH) if arch is None else arch


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
    config_file = root / configs[w["config"]]["file"]
    config = _load_json(config_file)
    if "arch" not in config:
        raise SpecError(f"{config_file} names no architecture (a top-level \"arch\")")
    arch = load_arch(root, config["arch"])
    traffic = _load_json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        arch=arch,
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


def kv_bytes_per_token(m: Dict[str, Any], arch=None) -> int:
    """KV cache bytes of one token over all layers."""
    return m["num_layers"] * resolve(arch).kv_bytes(m)


def pool_blocks(config: Dict[str, Any], traffic: Dict[str, Any], arch=None) -> int:
    """KV pool blocks: every slot at the widest table, or what the
    config's ``kv_pool_bytes`` holds, whichever is fewer. Admission
    reserves each request's own need, so a pool under the worst case
    only makes a request wait for blocks when the running ones hold more
    than the pool."""
    eng = config["engine"]
    bs = eng["kv_block_size"]
    worst = traffic["slots"] * widths(traffic, eng["prompt_bucket"])[-1] // bs
    budget = eng["kv_pool_bytes"] // (kv_bytes_per_token(config["model"], arch) * bs)
    return min(worst, budget)
