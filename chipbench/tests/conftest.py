"""Shared set-up of the benchmark's CPU tests: the benchmark's own
modules on the path, and a checkout made of new files only."""

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
# the tiny cells' mean-gap limit, from calibrate.py on the CPU at seeds
# 101-106: the program read at most 0.0024 (tiny.offline) and 0 (tiny.open),
# the float8 control at least 0.023 (tiny.offline) and 0.0082 (tiny.open)
TINY_LIMIT = 0.007
sys.path.insert(0, str(BENCH))


def tiny_checkout(tmp: Path, cells) -> Path:
    """A checkout holding the benchmark as it is, the program beside it,
    and, as new files only, the tiny config, the given tiny traffic mixes
    and their limits, with a BENCHMARK.json that names those cells."""
    shutil.copytree(BENCH, tmp / "chipbench", ignore=shutil.ignore_patterns("tests"))
    os.symlink(REPO / "src", tmp / "src")
    shutil.copy(DATA / "tiny.json", tmp / "chipbench" / "configs" / "tiny.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "https://arxiv.org/abs/2401.06066",
                             "file": "chipbench/configs/tiny.json", "reduced": [],
                             "why": "tiny"})
    for cell, mix in cells.items():
        shutil.copy(DATA / f"{mix}.json", tmp / "chipbench" / "traffic" / f"{mix}.json")
        (tmp / "chipbench" / "limits" / f"{cell}.json").write_text(json.dumps(
            {"sample_tokens": 12, "sample_most": 3, "limit": TINY_LIMIT}))
        bench["workloads"].append({"name": cell, "config": "tiny", "traffic": mix,
                                   "chips": 1, "why": "tiny"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture(autouse=True)
def _pallas(monkeypatch):
    # the Pallas kernels in interpret mode, as the chip runs them compiled
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
