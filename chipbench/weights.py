"""Random weights made on the device from the seed, in one jitted call.

The benchmark makes the weights itself, so that the reference can make
the same ones again after the window without taking anything from the
program. The tree is laid out as the serving engine takes it: every
per-layer leaf stacked on a leading layer axis. Norm scales are ones,
the embedding is N(0, 0.02^2), every other matrix N(0, 1/fan_in).
Stacked leaves are drawn one matrix at a time (``lax.map``), so making
them needs no temporary the size of a whole leaf.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


def shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes for the model section ``m`` of a config file."""
    L, d, V = m["num_layers"], m["d_model"], m["vocab_size"]
    hq, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    E, f = m["n_routed_experts"], m["moe_d_ff"]
    tree: Dict[str, Any] = {"embed": (V, d), "final_norm": (d,)}
    if not m["tie_embeddings"]:
        tree["lm_head"] = (d, V)
    moe = {
        "router": (L, d, E),
        "wi_gate": (L, E, d, f),
        "wi_up": (L, E, d, f),
        "wo": (L, E, f, d),
    }
    if m["n_shared_experts"]:
        sf = m["shared_d_ff"] * m["n_shared_experts"]
        moe.update(shared_wi_gate=(L, d, sf), shared_wi_up=(L, d, sf),
                   shared_wo=(L, sf, d))
    tree["layers"] = {
        "ln1": (L, d),
        "ln2": (L, d),
        "attn": {"wq": (L, d, hq * hd), "wk": (L, d, hkv * hd),
                 "wv": (L, d, hkv * hd), "wo": (L, hq * hd, d)},
        "moe": moe,
    }
    return tree


def _leaf(key, name: str, shape: Tuple[int, ...], dtype):
    if name in ("ln1", "ln2", "final_norm"):
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
def _maker(layout_key: str, dtype: str, sharding):
    import json

    tree = json.loads(layout_key)
    paths, treedef = jax.tree.flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, list))

    def build(key):
        leaves = []
        for i, (path, shape) in enumerate(paths):
            name = str(getattr(path[-1], "key", path[-1]))
            leaves.append(_leaf(jax.random.fold_in(key, i), name, tuple(shape),
                                jnp.dtype(dtype)))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=sharding)


def make(m: Dict[str, Any], seed: int, sharding) -> Dict[str, Any]:
    """The weights for seed ``seed``, placed by ``sharding``."""
    import json

    layout = json.dumps(shapes(m), sort_keys=True)
    return _maker(layout, m["dtype"], sharding)(jax.random.PRNGKey(seed))


def nbytes(m: Dict[str, Any]) -> int:
    n = sum(math.prod(s) for s in jax.tree.leaves(
        shapes(m), is_leaf=lambda x: isinstance(x, tuple)))
    return n * jnp.dtype(m["dtype"]).itemsize
