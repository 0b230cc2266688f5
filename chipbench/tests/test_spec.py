"""BENCHMARK.json and the files it names, read by name."""

import json
import re

import pytest
from conftest import BENCH, REPO

import spec

BENCH_JSON = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH_JSON["workloads"]])
def test_every_cell_loads_with_its_readers(cell):
    c = spec.load_cell(REPO, cell)
    assert c.traffic["slots"] > 0 and c.config["model"]["num_layers"] > 0
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    names = {m["name"] for m in c.end_to_end}
    for m in c.per_layer:
        assert callable(c.reader(m["name"]))
        assert m["moves"] in names  # the cell reports what the metric moves
    assert (REPO / "chipbench" / "limits" / f"{cell}.json").is_file()


def test_names_units_and_layers_keep_to_the_contract():
    metrics = BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in BENCH_JSON["workloads"]] \
        + [c["name"] for c in BENCH_JSON["configs"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert all(re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]) for m in metrics)
    assert all(len(w["why"]) <= 200 for w in BENCH_JSON["workloads"] + BENCH_JSON["configs"])
    for c in BENCH_JSON["configs"]:
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["source"] == c["source"] and conf["reduced"] == c["reduced"]


def test_unknown_cell_and_missing_reader_are_errors():
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell(REPO, "no.such.cell")
    c = spec.load_cell(REPO, BENCH_JSON["workloads"][0]["name"])
    with pytest.raises(spec.SpecError, match="no reader"):
        c.reader("no_such_metric")


def test_widths_and_pool():
    chat = json.loads((BENCH / "traffic" / "chat.json").read_text())
    assert spec.widths(chat, 512) == [1024, 1536, 2048, 2560]
    assert spec.table_width(1536, 511, 512) == 2048
    assert spec.table_width(1536, 512, 512) == 2560
    conf = json.loads((BENCH / "configs" / "deepseek-moe-16b.1chip.json").read_text())
    assert spec.kv_bytes_per_token(conf["model"]) == 4 * 2 * 16 * 128 * 2  # 32 KiB
    worst = 64 * 2560 // 16
    budget = conf["engine"]["kv_pool_bytes"] // (32768 * 16)
    assert spec.pool_blocks(conf, chat) == min(worst, budget)
