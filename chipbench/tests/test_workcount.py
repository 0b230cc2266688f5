"""The work counts against hand counts for both configurations."""

import json

import pytest
from conftest import BENCH

import workcount as wc


def _model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]


DS, MX = "deepseek-moe-16b.1chip", "mixtral-8x7b.1chip"

# per token and layer at no context: q,k,v,o projections + router +
# routed experts (top_k x gate/up/down) + shared experts
HAND_TOKEN = {
    DS: 2 * 2048 * (16 + 2 * 16) * 128 + 2 * 16 * 128 * 2048
    + 2 * 2048 * 64 + 6 * 3 * 2 * 2048 * 1408 + 2 * 3 * 2 * 2048 * 1408,
    MX: 2 * 4096 * (32 + 2 * 8) * 128 + 2 * 32 * 128 * 4096
    + 2 * 4096 * 8 + 2 * 3 * 2 * 4096 * 14336,
}
HAND_HEAD = {DS: 2 * 2048 * 102400, MX: 2 * 4096 * 32000}


@pytest.mark.parametrize("name", [DS, MX])
def test_token_and_head_flops(name):
    m = _model(name)
    assert wc.token_flops(m, 0) == HAND_TOKEN[name]
    hq, hd = m["num_heads"], m["head_dim"]
    assert wc.token_flops(m, 300) - wc.token_flops(m, 0) == 4 * hq * hd * 300
    assert wc.head_flops(m) == HAND_HEAD[name]


def test_sum_ctx():
    assert wc.sum_ctx(0, 3) == 1 + 2 + 3
    assert wc.sum_ctx(510, 512) == 511 + 512
    assert wc.sum_ctx(5, 5) == 0


@pytest.mark.parametrize("name", [DS, MX])
def test_step_flops(name):
    m = _model(name)
    L, hq, hd = m["num_layers"], m["num_heads"], m["head_dim"]
    # a fused step: prompt positions 600..1023 and two decoding rows
    got = wc.step_flops(m, (600, 1024), [10, 2000], chunk_sampled=False)
    core = 4 * hq * hd * (sum(p + 1 for p in range(600, 1024)) + 10 + 2000)
    want = L * ((424 + 2) * HAND_TOKEN[name] + core) + 2 * HAND_HEAD[name]
    assert got == want
    # a prompt's last chunk alone samples one token
    assert wc.step_flops(m, (0, 512), [], True) - wc.step_flops(m, (0, 512), [], False) \
        == HAND_HEAD[name]


def test_gmm_work_deepseek():
    m = _model(DS)
    f, b = wc.gmm_work(m, 64)  # 64 x 6 = 384 = 6 E picks: every expert read
    assert f == 64 * 6 * 3 * 2 * 2048 * 1408
    assert b == 3 * 64 * 2048 * 1408 * 2  # 1.1 GB per layer
    assert wc.gmm_work(m, 63)[1] == 0  # too few picks to count every expert
    assert wc.gmm_work(m, 512)[0] == 512 * 6 * 3 * 2 * 2048 * 1408


def test_gmm_work_mixtral():
    m = _model(MX)
    f, b = wc.gmm_work(m, 64)
    assert f == 64 * 2 * 3 * 2 * 4096 * 14336
    assert b == 3 * 8 * 4096 * 14336 * 2  # 2.8 GB per layer
    assert wc.gmm_work(m, 24)[1] == b and wc.gmm_work(m, 23)[1] == 0


@pytest.mark.parametrize("name", [DS, MX])
def test_paged_attn_work(name):
    m = _model(name)
    L, hq, hkv, hd = m["num_layers"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    f, b = wc.paged_attn_work(m, [100, 2560])
    assert f == L * 4 * hq * hd * (100 + 2560)
    assert b == L * (100 + 2560) * 2 * hkv * hd * 2  # K and V, bfloat16


def test_least_time_takes_the_slower_bound():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert wc.least_time(197e12, 0, peaks) == pytest.approx(1.0)
    assert wc.least_time(1.0, 819e9, peaks) == pytest.approx(1.0)
