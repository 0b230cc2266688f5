"""Architectures are files: what moved into ``arch/moe_gqa.py`` gives,
bitwise, what the benchmark gave before the move (``data/arch_parity.json``,
captured from the code before it), and an architecture added as new files
alone runs a whole cell."""

import hashlib
import json
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding
from conftest import BENCH, DATA, REPO, tiny_checkout

import reference
import run
import spec
import weights
import workcount as wc

PARITY = json.loads((DATA / "arch_parity.json").read_text())
CELLS = {"dsmoe16b.chat": "deepseek-moe-16b.1chip",
         "mixtral8x7b.decode": "mixtral-8x7b.1chip"}


def _conf(name):
    path = DATA / "tiny.json" if name == "tiny" else BENCH / "configs" / f"{name}.json"
    conf = json.loads(path.read_text())
    return conf["model"], spec.load_arch(REPO, conf["arch"])


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PARITY["leaves"]))
def test_leaves_keep_their_paths_shapes_and_order(name):
    m, arch = _conf(name)
    leaves = jax.tree.leaves_with_path(arch.shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    assert [[jax.tree_util.keystr(p), list(s)] for p, s in leaves] == PARITY["leaves"][name]


@pytest.mark.parametrize("seed", sorted(PARITY["weights_sha256"]))
def test_tiny_weights_are_bitwise_the_same(seed):
    m, arch = _conf("tiny")
    params = weights.make(m, int(seed), SingleDeviceSharding(jax.devices()[0]), arch)
    assert _digest(jax.tree.leaves(params)) == PARITY["weights_sha256"][seed]


@pytest.mark.parametrize("precision", ["f32", "fp8"])
def test_tiny_reference_logits_are_bitwise_the_same(precision):
    m, arch = _conf("tiny")
    params = weights.make(m, 5, SingleDeviceSharding(jax.devices()[0]), arch)
    toks = np.random.default_rng(2).integers(1, m["vocab_size"], 40)
    got = reference.logits_at(params, m, toks, range(40), precision, arch)
    assert _digest([got]) == PARITY["logits_sha256"][precision]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_pools_and_kv_bytes_are_the_same(cell):
    c = spec.load_cell(REPO, cell)
    assert spec.pool_blocks(c.config, c.traffic, c.arch) == PARITY["pool_blocks"][cell]
    assert spec.kv_bytes_per_token(c.config["model"], c.arch) \
        == PARITY["kv_bytes_per_token"][CELLS[cell]]


@pytest.mark.parametrize("name", sorted(PARITY["work"]))
def test_work_counts_are_the_same(name):
    m, arch = _conf(name)
    got = {
        "token_flops_0": wc.token_flops(m, 0, arch),
        "token_flops_300": wc.token_flops(m, 300, arch),
        "head_flops": wc.head_flops(m),
        "step_fused": wc.step_flops(m, (600, 1024), [10, 2000], False, arch),
        "step_chunk_sampled": wc.step_flops(m, (0, 512), [], True, arch),
        "step_decode": wc.step_flops(m, (0, 0), [17, 33, 4096], False, arch),
        "gmm_64": list(wc.gmm_work(m, 64)), "gmm_512": list(wc.gmm_work(m, 512)),
        "gmm_7": list(wc.gmm_work(m, 7)),
        "paged_attn": list(wc.paged_attn_work(m, [100, 2560], arch)),
    }
    assert got == PARITY["work"][name]


def test_no_shared_module_names_an_attention_width():
    for path in BENCH.glob("*.py"):
        assert not re.search(r"num_kv_heads|head_dim|num_heads", path.read_text()), path


@pytest.mark.parametrize("arch", [None, "no_such_arch"])
def test_a_config_without_its_architecture_is_a_spec_error(tmp_path, arch):
    root = tiny_checkout(tmp_path, {"tiny.offline": "tiny_offline"})
    path = root / "chipbench" / "configs" / "tiny.json"
    conf = json.loads(path.read_text())
    if arch is None:
        del conf["arch"]
    else:
        conf["arch"] = arch
    path.write_text(json.dumps(conf))
    with pytest.raises(spec.SpecError, match="architecture"):
        spec.load_cell(root, "tiny.offline")


def test_an_architecture_added_as_files_runs_a_cell_correct(tmp_path, capsys):
    """A copy of moe_gqa.py under another name, and a config naming it,
    through ``run.main`` on the CPU (the look for a chip skipped). The
    cell also reports ``prompt_tok_s``, as the offline long-prompt cell
    does."""
    root = tiny_checkout(tmp_path, {"tiny.toy": "tiny_offline"}, arch="toy_arch")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"] = [m for m in bench["end_to_end"] if m["name"] != "prompt_tok_s"]
    bench["end_to_end"].append({"name": "prompt_tok_s", "unit": "tokens/s", "better": "higher",
                                "bound": 0.02, "source": "host_clock", "workloads": ["tiny.toy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(root, "tiny.toy")
    assert cell.arch.__file__ == str((root / "chipbench" / "arch" / "toy_arch.py").resolve())
    rc = run.main(["--workload", "tiny.toy", "--seed", "3000000007", "--seconds", "2",
                   "--trace", "0"], require_tpu=False, root=root)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert res["checks"]["reference_kernel_branches"]["value"] == 0
    assert res["metrics"]["prompt_tok_s"]["value"] > 0
