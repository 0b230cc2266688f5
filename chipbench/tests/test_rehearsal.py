"""CPU rehearsals of whole runs at a tiny size: the open-loop and the
offline loops end to end through ``run.main`` (the look for a chip
skipped), a cell made of new files only, a timed path broken underneath,
and the runs that must fail without a result line."""

import json
import os
import subprocess
import sys

import pytest
from conftest import BENCH, REPO, TINY_LIMIT, tiny_checkout

import calibrate
import check
import run

CELLS = {"tiny.open": "tiny_open", "tiny.offline": "tiny_offline"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("checkout"), CELLS)


def _run(checkout, capsys, cell, trace=0, seed=3_000_000_001):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                   "--trace", str(trace)], require_tpu=False, root=checkout)
    out = capsys.readouterr()
    return rc, out.out.strip().splitlines(), out.err.strip().splitlines()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_run_prints_its_result_last(checkout, capsys, monkeypatch, cell):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    rc, out, err = _run(checkout, capsys, cell)
    assert rc == 0
    res = json.loads(out[-1])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    want = {"tbt_p95_ms", "setup_s"}
    assert want <= set(res["metrics"])
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    # each number compared ends standard error, beside its limit
    assert [line.split(":")[0] for line in err[-len(res["checks"]):]] == [
        f"check {k}" for k in res["checks"]]
    assert any(line == "compilations inside the window: 0" for line in out)
    assert res["checks"]["window_compilations"]["value"] == 0
    assert res["checks"]["step_kind_drift"]["value"] == 0


def test_a_token_altered_where_it_is_produced_is_not_correct(checkout, capsys, monkeypatch):
    """The timed path broken underneath: every sampled token moved by one."""
    from repro.serving import engine

    real = engine.sample
    monkeypatch.setattr(engine, "sample", lambda *a, **k: (real(*a, **k) + 1) % 256)
    rc, out, err = _run(checkout, capsys, "tiny.offline")
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is False
    gap = res["checks"][check.COMPARED]
    assert gap["value"] > gap["limit"] == TINY_LIMIT
    assert res["checks"]["step_kind_drift"]["value"] == 0


def test_the_float8_control_reads_wider_gaps_than_the_program(checkout, capsys):
    """The control at a size a test run holds, on three seeds: by the
    test ``run.py`` uses and the cell's own limit, the program is correct
    on every seed and the control on none."""
    rc = calibrate.main(["--workload", "tiny.offline", "--seconds", "2", "--seeds",
                         "101", "102", "103"], require_tpu=False, root=checkout)
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    assert rc == 0 and len(rows) == 3 + len(check.STATISTICS) + 1
    for r in rows[:3]:
        assert r["limit"] == TINY_LIMIT
        assert r["correct"] is True and r["control_correct"] is False
        assert r["control"][check.COMPARED] > r["served"][check.COMPARED]
    assert rows[-1]["compared"] == check.COMPARED
    assert rows[-1]["program_correct_seeds"] == 3 and rows[-1]["control_correct_seeds"] == 0


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", "--workload", "dsmoe16b.chat",
                           "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_fails_without_a_result_line():
    p = _cli(REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_benchmark_alone_fails_without_a_result_line(tmp_path):
    import shutil

    shutil.copytree(BENCH, tmp_path / "chipbench")
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert "not beside the benchmark" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
