"""Process set-up shared by the entry points that drive a device.

``use_compile_cache`` keeps JAX's persistent compilation cache at one
fixed place, so repeated runs of ``launch/serve.py`` and ``chip_smoke.py``
from a checkout reuse compiled programs. ``planner_chip`` names the
planner's hardware model for the accelerator JAX reports.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout this package was imported from (``<checkout>/src/repro``)
CHECKOUT = Path(__file__).resolve().parents[3]

# jax ``device_kind`` -> planner chip (``repro.core.hardware.CHIPS``)
DEVICE_KIND_CHIPS = {
    "TPU v5 lite": "tpu_v5e",
}


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set, in which case JAX reads
    that variable itself and nothing is changed. Call before the first
    compile; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def planner_chip(device) -> str:
    """The planner chip name for a JAX device; an accelerator the table
    does not know is an error, never a default."""
    try:
        return DEVICE_KIND_CHIPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no planner chip model for device kind {device.device_kind!r} "
            f"(known: {sorted(DEVICE_KIND_CHIPS)})"
        ) from None
