"""The multi-head latent attention architecture (``arch/mla_moe.py``,
DeepSeek-V2-Lite) as files: its weight layout is the program's, its
float32 logits are the program's own reference's, its byte and operation
counts are the hand values at the published widths, and a tiny MLA cell
made of new files runs through ``run.py`` on the CPU to ``correct``."""

import json

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

ARCH = spec.load_arch(REPO, "mla_moe")
TINY = json.loads((DATA / "tiny_mla.json").read_text())
REAL = json.loads((BENCH / "configs" / "deepseek-v2-lite.1chip.json").read_text())


@pytest.mark.parametrize("conf", [TINY, REAL], ids=["tiny", "deepseek-v2-lite"])
def test_shapes_are_the_programs_parameter_layout(conf):
    from repro.models import param_shapes

    want = jax.tree.leaves_with_path(param_shapes(run.program_config(conf)),
                                     is_leaf=lambda x: isinstance(x, tuple))
    got = jax.tree.leaves_with_path(ARCH.shapes(conf["model"]),
                                    is_leaf=lambda x: isinstance(x, tuple))
    assert [(p, tuple(s)) for p, s in got] == [(p, tuple(s)) for p, s in want]


def test_float32_logits_are_the_programs_reference():
    """The same seeded weights through this module's float32 stack (query
    blocks, one layer at a time) and the program's own plain reference."""
    from repro.models.reference import reference_logits

    m = TINY["model"]
    params = weights.make(m, 11, SingleDeviceSharding(jax.devices()[0]), ARCH)
    toks = np.random.default_rng(3).integers(1, m["vocab_size"], 600)
    got = reference.logits_at(params, m, toks, range(600), "f32", ARCH)
    want = reference_logits(params, run.program_config(TINY), toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_bytes_and_counts_at_the_published_widths():
    m = REAL["model"]
    assert ARCH.kv_bytes(m) == 576 * 2 == ARCH.attn_cache_bytes(m)
    assert spec.kv_bytes_per_token(m, ARCH) == 5 * 1152
    d, H = 2048, 16
    assert ARCH.attn_proj_flops(m) == (2 * d * H * 192 + 2 * d * 576 + 2 * 512 * H * 256
                                       + 2 * H * 128 * d)
    assert ARCH.attn_core_flops(m) == 2 * 16 * (576 + 512)
    flops, nbytes = wc.paged_attn_work(m, [4097, 12000], ARCH)
    assert nbytes == 5 * (4097 + 12000) * 1152
    assert flops == 5 * (4097 + 12000) * 34816
    # the cell's pool: every slot at the widest table, 25,600 blocks of 16
    cell = spec.load_cell(REPO, "dsv2lite.longctx")
    assert spec.widths(cell.traffic, 512)[-1] == 12800  # an offline mix warms the widest
    assert spec.pool_blocks(cell.config, cell.traffic, cell.arch) == 32 * 12800 // 16
    assert set(ARCH.KERNELS) == {"mla_decode", "gmm"} and "kv_norm" in ARCH.ONES


def test_a_tiny_mla_cell_runs_correct(tmp_path, capsys):
    """A tiny MLA configuration (a dense first layer, two MoE layers, YaRN
    that ramps inside its rope block) through ``run.main`` on the CPU, the
    Pallas kernels in interpret mode: correct, both kernel families taken."""
    root = tiny_checkout(tmp_path, {"tiny.mla": "tiny_offline"})
    (root / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    rc = run.main(["--workload", "tiny.mla", "--seed", "3000000011", "--seconds", "2",
                   "--trace", "0"], require_tpu=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert res["checks"]["reference_kernel_branches"]["value"] == 0
    assert any("mla_decode.pallas" in line for line in out if line.startswith("dispatch"))
