"""Entry-point set-up: the planner chip for a JAX device kind, and where
JAX's persistent compilation cache lands (checked in child processes,
which pin the CPU, so this process's JAX config stays untouched)."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch import runtime

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


def test_planner_chip_maps_v5e_and_refuses_unknown_kinds():
    assert runtime.planner_chip(_Dev("TPU v5 lite")) == "tpu_v5e"
    with pytest.raises(ValueError, match="TPU v9"):
        runtime.planner_chip(_Dev("TPU v9"))


def _compile_in_child(env_dir):
    """Compile one fresh program after ``use_compile_cache``; returns the
    cache directory the child reports."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = textwrap.dedent("""
        import os
        from repro.launch.runtime import use_compile_cache
        path = use_compile_cache()
        import jax, jax.numpy as jnp
        n = 1000 + int.from_bytes(os.urandom(2), "little")  # a new program
        jax.jit(lambda x: jnp.cos(x) * 7 - 2)(jnp.arange(n, dtype=jnp.float32))
        print(path)
    """)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    return r.stdout.strip().splitlines()[-1]


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_compile_cache_defaults_to_the_checkout():
    default = os.path.join(ROOT, ".jax_cache")
    before = _entries(default)
    assert _compile_in_child(None) == default
    assert _entries(default) - before


def test_compile_cache_follows_the_environment_only(tmp_path):
    default = os.path.join(ROOT, ".jax_cache")
    before = _entries(default)
    assert _compile_in_child(tmp_path) == str(tmp_path)
    assert _entries(tmp_path)
    assert _entries(default) == before
