"""Random weights made on the device from the seed, in one jitted call.

The benchmark makes the weights itself, so that the reference can make
the same ones again after the window without taking anything from the
program. The tree is the architecture module's ``shapes`` (``spec.py``),
laid out as the serving engine takes it: every per-layer leaf stacked on
a leading layer axis. The leaves the module names in ``ONES`` (norm
scales) are ones, the embedding is N(0, 0.02^2), every other matrix
N(0, 1/fan_in).
Stacked leaves are drawn one matrix at a time (``lax.map``), so making
them needs no temporary the size of a whole leaf.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

import spec


def _leaf(key, name: str, shape: Tuple[int, ...], dtype, ones: Tuple[str, ...]):
    if name in ones:
        return jnp.ones(shape, dtype)
    std = 0.02 if name == "embed" else 1.0 / math.sqrt(shape[-2])
    mat = shape[-2:]
    n = math.prod(shape[:-2])
    if n == 1:
        return (jax.random.normal(key, mat, jnp.float32) * std).astype(dtype)
    keys = jax.random.split(key, n)
    out = jax.lax.map(
        lambda k: (jax.random.normal(k, mat, jnp.float32) * std).astype(dtype), keys)
    return out.reshape(shape)


@functools.lru_cache(maxsize=None)
def _maker(layout_key: str, dtype: str, sharding, ones: Tuple[str, ...]):
    import json

    tree = json.loads(layout_key)
    paths, treedef = jax.tree.flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, list))

    def build(key):
        leaves = []
        for i, (path, shape) in enumerate(paths):
            name = str(getattr(path[-1], "key", path[-1]))
            leaves.append(_leaf(jax.random.fold_in(key, i), name, tuple(shape),
                                jnp.dtype(dtype), ones))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=sharding)


def make(m: Dict[str, Any], seed: int, sharding, arch=None) -> Dict[str, Any]:
    """The weights for seed ``seed``, placed by ``sharding``."""
    import json

    arch = spec.resolve(arch)
    layout = json.dumps(arch.shapes(m), sort_keys=True)
    return _maker(layout, m["dtype"], sharding, tuple(arch.ONES))(jax.random.PRNGKey(seed))


def nbytes(m: Dict[str, Any], arch=None) -> int:
    n = sum(math.prod(s) for s in jax.tree.leaves(
        spec.resolve(arch).shapes(m), is_leaf=lambda x: isinstance(x, tuple)))
    return n * jnp.dtype(m["dtype"]).itemsize
