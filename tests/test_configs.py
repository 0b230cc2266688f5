"""Config registry and parameter-count checks against published figures."""
import pytest

from repro.configs import ASSIGNED_ARCHS, PAPER_ARCHS, get_config, list_configs


def test_all_configs_load():
    assert len(list_configs()) == 14
    for name in ASSIGNED_ARCHS + PAPER_ARCHS:
        cfg = get_config(name)
        assert cfg.num_layers > 0 and cfg.d_model > 0


# published parameter counts (B), +-8% tolerance
PUBLISHED = {
    "deepseek-moe-16b": 16.4,
    "gemma3-27b": 27.0,
    "mistral-nemo-12b": 12.2,
    "qwen3-moe-30b-a3b": 30.5,
    "gemma-7b": 8.5,
    "falcon-mamba-7b": 7.3,
    "gemma2-9b": 9.2,
    "mixtral-8x7b": 46.7,
    "qwen2-57b-a14b": 57.4,
    "hymba-1.5b": 1.5,
}


@pytest.mark.parametrize("name,expected", sorted(PUBLISHED.items()))
def test_param_counts_match_published(name, expected):
    total = get_config(name).total_params() / 1e9
    assert abs(total - expected) / expected < 0.10, (name, total, expected)


ACTIVE = {
    "deepseek-moe-16b": 2.8,
    "qwen3-moe-30b-a3b": 3.3,
    "mixtral-8x7b": 12.9,
    "qwen2-57b-a14b": 14.2,
}


@pytest.mark.parametrize("name,expected", sorted(ACTIVE.items()))
def test_active_params(name, expected):
    active = get_config(name).active_params_per_token() / 1e9
    assert abs(active - expected) / expected < 0.15, (name, active)


def test_reduced_variants_are_small():
    for name in ASSIGNED_ARCHS:
        r = get_config(name).reduced()
        assert r.num_layers <= 2
        assert r.d_model <= 512
        if r.is_moe:
            assert r.n_routed_experts <= 4


def test_divisibility_of_shardable_dims():
    # every assigned arch must have d_ff / experts shardable or a fallback
    for name in ASSIGNED_ARCHS:
        cfg = get_config(name)
        if cfg.d_ff:
            assert cfg.d_ff % 16 == 0 or not cfg.has_attention
        if cfg.is_moe:
            assert cfg.moe_d_ff % 16 == 0
