"""The benchmark's float32 reference equals the repository's own at a
tiny size, and its float8 control departs from it."""

import dataclasses
import json

import jax
import numpy as np
from jax.sharding import SingleDeviceSharding
from conftest import DATA

import reference
import weights


def _tiny():
    return json.loads((DATA / "tiny.json").read_text())


def test_reference_equals_the_repository_reference():
    from repro.configs import get_config
    from repro.models.reference import reference_logits

    conf = _tiny()
    m = dict(conf["model"], dtype="float32")
    cfg = dataclasses.replace(get_config(conf["registry"]), **m)
    params = weights.make(m, 11, SingleDeviceSharding(jax.devices()[0]))
    toks = np.random.default_rng(0).integers(1, m["vocab_size"], 37)
    want = np.asarray(reference_logits(params, cfg, toks))
    got = np.asarray(reference.reference_logits(params, m, toks))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_rows_are_read_where_asked_and_padding_changes_nothing():
    m = _tiny()["model"]
    params = weights.make(m, 3, SingleDeviceSharding(jax.devices()[0]))
    toks = np.random.default_rng(1).integers(1, m["vocab_size"], 21)
    full = np.asarray(reference.reference_logits(params, m, toks))
    some = np.asarray(reference.logits_at(params, m, toks, [3, 20, 7]))
    np.testing.assert_allclose(some, full[[3, 20, 7]], rtol=1e-6, atol=1e-6)


def test_float8_control_departs_from_float32():
    m = _tiny()["model"]
    params = weights.make(m, 5, SingleDeviceSharding(jax.devices()[0]))
    toks = np.random.default_rng(2).integers(1, m["vocab_size"], 40)
    f32 = np.asarray(reference.logits_at(params, m, toks, range(40)))
    fp8 = np.asarray(reference.logits_at(params, m, toks, range(40), "fp8"))
    rel = np.linalg.norm(fp8 - f32, axis=-1) / np.linalg.norm(f32, axis=-1)
    assert 0.01 < np.median(rel) < 0.5


def test_weights_are_a_function_of_the_seed():
    m = _tiny()["model"]
    a = weights.make(m, 2**31 + 17, SingleDeviceSharding(jax.devices()[0]))
    b = weights.make(m, 2**31 + 17, SingleDeviceSharding(jax.devices()[0]))
    c = weights.make(m, 2**31 + 18, SingleDeviceSharding(jax.devices()[0]))
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b), jax.tree.leaves(c)):
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
    assert not np.array_equal(np.asarray(a["embed"], np.float32),
                              np.asarray(c["embed"], np.float32))
