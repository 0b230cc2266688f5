"""``chip_smoke.py``'s checks, exercised on the CPU: every condition the
smoke must refuse (no TPU, a planner fallback, a background error, a
session that hides solver crashes behind static TP, a kernel family that
did not trace its Pallas branch), the engine-vs-reference logit check
at a small size, and the script's exit without a TPU or without the rest
of the repository."""
import dataclasses
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import reduced
from repro.core import HAPSession, fixed_plan
from repro.models import init_params
from repro.serving.engine import EngineStats

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)
import chip_smoke as cs  # noqa: E402


class _Tpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_check_device_refuses_cpu_and_missing_chips():
    with pytest.raises(cs.SmokeFailure, match="no TPU"):
        cs.check_device(jax.devices(), 1)
    cs.check_device([_Tpu()], 1)
    with pytest.raises(cs.SmokeFailure, match="4 chips"):
        cs.check_device([_Tpu()], 4)


@pytest.mark.parametrize("field", ["background_errors", "planner_fallbacks"])
def test_check_stats_refuses_degraded_runs(field):
    cs.check_stats(EngineStats())
    with pytest.raises(cs.SmokeFailure):
        cs.check_stats(EngineStats(**{field: 1}))


def test_check_session_refuses_static_tp_fallback():
    cfg = reduced("deepseek-moe-16b")
    cs.check_session(HAPSession(cfg, "a6000", 1, fallback=""))
    with pytest.raises(cs.SmokeFailure, match="'tp'"):
        cs.check_session(HAPSession(cfg, "a6000", 1, fallback="tp"))
    with pytest.raises(cs.SmokeFailure):
        cs.check_session(HAPSession(cfg, "a6000", 1))  # the default


def test_check_dispatch_requires_every_pallas_family_and_no_reference():
    fams = ("decode", "flash", "gmm")
    ok = {"decode.pallas": 4, "flash.pallas_shard_map": 1, "gmm.pallas": 12}
    cs.check_dispatch(ok, fams)
    with pytest.raises(cs.SmokeFailure, match="flash"):
        cs.check_dispatch({"decode.pallas": 4, "gmm.pallas": 12}, fams)
    # a quiet reference branch fails even beside the kernels
    for quiet in ("decode.ref_paged", "flash.ref", "gmm.ref"):
        with pytest.raises(cs.SmokeFailure, match=quiet):
            cs.check_dispatch(dict(ok, **{quiet: 1}), fams)


@pytest.fixture(scope="module")
def f32_engine():
    base = reduced("deepseek-moe-16b")
    # dropless capacity, as the smoke serves it
    cfg = dataclasses.replace(
        base, capacity_factor=base.n_routed_experts / base.top_k)
    params = init_params(cfg, jax.random.PRNGKey(0))
    session = HAPSession(cfg, "a6000", 1, source=fixed_plan("TP1", "TP1"),
                         fallback="", prompt_bucket=16, gen_bucket=8)
    return session.engine(params, max_batch=4, kernel_backend="pallas")


def test_engine_logits_match_reference_and_the_check_catches_a_shift(
        f32_engine):
    """In float32 the engine's prefill (flash and paged chunk) and its
    decode steps through the paged cache agree with the plain reference
    to rounding; the smoke's check passes them and refuses logits taken
    one position off."""
    cfg = f32_engine.cfg
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, cfg.vocab_size, 16).tolist()
    follow = rng.integers(1, cfg.vocab_size, 3).tolist()
    got = cs.engine_logits(f32_engine, prompt, follow)
    assert set(got) == {"prefill_flash", "prefill_paged", "decode_1",
                        "decode_2", "decode_3"}
    want = cs.reference_at(f32_engine.params, cfg, prompt, follow, got)
    errs = cs.check_logits("f32", got, want)
    assert max(errs.values()) < 1e-4, errs  # float32: rounding only
    shifted = dict(want, decode_2=want["decode_3"], decode_3=want["decode_2"],
                   decode_1=want["prefill_flash"])
    with pytest.raises(cs.SmokeFailure):
        cs.check_logits("shifted", got, shifted)


def _run_smoke(cwd, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_over)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_smoke_without_tpu_exits_nonzero_without_a_result():
    r = _run_smoke(ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_smoke_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run_smoke(tmp_path, PYTHONPATH="")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
