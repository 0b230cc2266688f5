"""EP micro-batch pipelining (the EPS-MoE schedule, DESIGN.md §4e).

The dispatch buffer splits into K capacity slabs so each slab's
all_to_all overlaps the previous slab's expert FFN. Routing and
capacity are assigned on the FULL local batch before the split, so K
must only reshape the schedule — these tests pin token-exactness
across K (including a K that does not divide the capacity), across
kernel backends, and on a real EP2 mesh through the serving engine.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced
from repro.core.flops import Workload
from repro.core.latency import ep_pipeline_chunks, overlapped_comm
from repro.core.strategy import ExpertStrategy
from repro.kernels import ops
from repro.models import moe as moe_mod
from repro.sharding.specs import make_plan

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _cfg():
    # no shared experts: apply_moe then exercises only the routed path
    return reduced("deepseek-moe-16b", capacity_factor=8.0,
                   n_shared_experts=0)


def _moe_params(cfg):
    d, E, f = cfg.d_model, cfg.n_routed_experts, cfg.moe_d_ff
    return {
        "router": jax.random.normal(jax.random.PRNGKey(6), (d, E)) * .1,
        "wi_gate": jax.random.normal(jax.random.PRNGKey(7), (E, d, f)) * .05,
        "wi_up": jax.random.normal(jax.random.PRNGKey(8), (E, d, f)) * .05,
        "wo": jax.random.normal(jax.random.PRNGKey(9), (E, f, d)) * .05,
    }


# ---------------------------------------------------------------------------
# pipeline-depth resolution
# ---------------------------------------------------------------------------
def test_pipeline_chunks_resolver():
    pc = moe_mod.pipeline_chunks
    # knob=1 forces the serial schedule everywhere
    assert pc(64, 4, 1) == 1
    assert pc(8, 1, 1) == 1
    # a forced K>=2 applies even on ep=1 meshes (the a2a degenerates to
    # the identity there, which is what the parity tests exploit), but
    # never exceeds the capacity
    assert pc(64, 1, 4) == 4
    assert pc(8, 2, 16) == 8
    # auto: serial without an EP axis; else the deepest K in {4, 2} that
    # keeps every slab at least one capacity round (8) wide
    assert pc(64, 1, 0) == 1
    assert pc(32, 2, 0) == 4
    assert pc(16, 2, 0) == 2
    assert pc(8, 2, 0) == 1


def test_latency_mirror_matches_runtime_resolver():
    """ep_pipeline_chunks (the planner's view) must agree with the
    runtime resolver for the capacity it predicts, or the ILP prices a
    schedule the engine never runs."""
    cfg = _cfg()
    for knob in (0, 1, 2, 4):
        for e in (ExpertStrategy(tp=1, ep=1), ExpertStrategy(tp=1, ep=2),
                  ExpertStrategy(tp=1, ep=4)):
            for phase, w in (("prefill", Workload(batch=4, prompt=256,
                                                  gen=32)),
                             ("decode", Workload(batch=4, prompt=256,
                                                 gen=32))):
                t_loc = max(w.tokens(phase) // max(4 // e.tp, 1), 1)
                c_loc = moe_mod.capacity(t_loc, cfg)
                assert ep_pipeline_chunks(cfg, w, phase, e, 4, knob) == \
                    moe_mod.pipeline_chunks(c_loc, e.ep, knob), (knob, e,
                                                                 phase)


def test_overlapped_comm_model():
    # K=1 (or zero comm) is the serial cost
    assert overlapped_comm(10.0, 3.0, 1) == 10.0
    assert overlapped_comm(0.0, 3.0, 4) == 0.0
    # compute fully hides all but the first chunk's exchange
    assert overlapped_comm(8.0, 100.0, 4) == pytest.approx(2.0)
    # comm-bound: exposed cost approaches t_comm from below, never under
    # the t_comm/K floor, and deeper pipelines never cost more
    t2 = overlapped_comm(8.0, 1.0, 2)
    t4 = overlapped_comm(8.0, 1.0, 4)
    assert 8.0 / 4 <= t4 <= t2 <= 8.0


# ---------------------------------------------------------------------------
# token-exactness across K and backends (single-device mesh: the slab
# all_to_alls degenerate to identities, isolating the schedule change)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("K", [2, 3, 4])
def test_pipelined_ep_matches_serial(K, backend):
    cfg = _cfg()
    moe_p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 13, cfg.d_model),
                          jnp.float32)
    mesh = jax.make_mesh((1,), ("model",))
    plan = make_plan(mesh, cfg, expert_mode="ep")
    assert plan.ffn_mode == "ep"
    serial = moe_mod.apply_moe(
        x, moe_p, cfg, dataclasses.replace(plan, moe_pipeline=1),
        backend=backend)
    ops.reset_dispatch_counts()
    piped = moe_mod.apply_moe(
        x, moe_p, cfg, dataclasses.replace(plan, moe_pipeline=K),
        backend=backend)
    assert ops.DISPATCH_COUNTS.get(f"moe.ep_pipeline_k{K}", 0) >= 1
    np.testing.assert_allclose(np.asarray(piped.y), np.asarray(serial.y),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(piped.aux_loss),
                               np.asarray(serial.aux_loss), atol=1e-6)


def test_non_dividing_chunk_count_covers_all_slots():
    """K=3 against a capacity of 16: slabs of 6/5/5 — the bounds must
    tile the capacity exactly (no slot dropped or doubled)."""
    cfg = _cfg()
    T = 16  # padded local tokens
    C = moe_mod.capacity(T, cfg)
    assert C % 3 != 0  # the interesting case
    moe_p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, T, cfg.d_model),
                          jnp.float32)
    mesh = jax.make_mesh((1,), ("model",))
    plan = make_plan(mesh, cfg, expert_mode="ep")
    serial = moe_mod.apply_moe(
        x, moe_p, cfg, dataclasses.replace(plan, moe_pipeline=1))
    piped = moe_mod.apply_moe(
        x, moe_p, cfg, dataclasses.replace(plan, moe_pipeline=3))
    np.testing.assert_allclose(np.asarray(piped.y), np.asarray(serial.y),
                               atol=1e-5)


def test_serial_schedule_records_probe():
    cfg = _cfg()
    moe_p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, cfg.d_model))
    mesh = jax.make_mesh((1,), ("model",))
    plan = make_plan(mesh, cfg, expert_mode="ep")
    ops.reset_dispatch_counts()
    moe_mod.apply_moe(x, moe_p, cfg,
                      dataclasses.replace(plan, moe_pipeline=1))
    assert ops.DISPATCH_COUNTS.get("moe.ep_serial", 0) >= 1


def test_pipelined_ffn_clamps_chunks_to_capacity():
    """K is clamped to the capacity: a 2-slot buffer with K=8 must run
    (as K=2), not emit empty slabs. pipelined_ep_ffn requires an EP
    shard_map context, so wrap one over a 1-wide mesh."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("model",))
    fn = jax.shard_map(
        lambda b: ops.pipelined_ep_ffn(b, lambda s: s * 2.0,
                                       ep_axis="model", chunks=8),
        mesh=mesh, in_specs=P("model"), out_specs=P("model"),
        check_vma=False)
    ops.reset_dispatch_counts()
    out = fn(jnp.ones((4, 2, 8)))
    assert out.shape == (4, 2, 8)
    assert ops.DISPATCH_COUNTS.get("moe.ep_pipeline_k2", 0) >= 1
    np.testing.assert_allclose(np.asarray(out), 2.0)


# ---------------------------------------------------------------------------
# ppermute-decomposed all_to_all (the double-buffer building block)
# ---------------------------------------------------------------------------
def test_a2a_ppermute_identity_on_single_device():
    """n=1 degenerates to the identity — the exact value the null-mesh
    parity tests above rely on."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("model",))
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 6, 8))
    fn = jax.shard_map(
        lambda b: ops.a2a_ppermute(b, "model", split=0, concat=1),
        mesh=mesh, in_specs=P("model"), out_specs=P("model"),
        check_vma=False)
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))


@pytest.mark.slow
def test_a2a_ppermute_matches_lax_all_to_all():
    """On a real 4-device mesh the explicit ppermute hop schedule must
    reproduce ``lax.all_to_all`` bit-exactly in both orientations
    (dispatch split=0/concat=1, combine split=1/concat=0) and round-trip
    to the identity; a non-dividing split dim must raise."""
    r = _run("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.kernels import ops

        mesh = jax.make_mesh((4,), ('ep',))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 12, 3))

        def wrap(f):
            return jax.shard_map(f, mesh=mesh, in_specs=P('ep'),
                                 out_specs=P('ep'), check_vma=False)

        for split, concat in ((0, 1), (1, 0)):
            mine = wrap(lambda b: ops.a2a_ppermute(
                b[0], 'ep', split=split, concat=concat)[None])(x)
            ref = wrap(lambda b: jax.lax.all_to_all(
                b[0], 'ep', split_axis=split, concat_axis=concat,
                tiled=True)[None])(x)
            np.testing.assert_array_equal(np.asarray(mine),
                                          np.asarray(ref))

        rt = wrap(lambda b: ops.a2a_ppermute(
            ops.a2a_ppermute(b[0], 'ep', split=0, concat=1),
            'ep', split=1, concat=0)[None])(x)
        np.testing.assert_array_equal(np.asarray(rt), np.asarray(x))

        try:
            wrap(lambda b: ops.a2a_ppermute(
                b[0], 'ep', split=2, concat=1)[None])(x)
        except ValueError as e:
            assert 'not divisible' in str(e), e
        else:
            raise AssertionError('non-dividing split must raise')
        print('OK')
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# real EP2 mesh through the serving engine (subprocess: forced host
# devices must not leak into the main pytest process)
# ---------------------------------------------------------------------------
def _run(code: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=900)


@pytest.mark.slow
def test_ep2_mesh_engine_token_exact_pipelined_vs_serial():
    """Greedy decode through the engine on a 2-device EP mesh: every
    pipeline depth must emit the serial schedule's exact tokens."""
    r = _run("""
        import dataclasses, jax
        from repro.configs import get_config
        from repro.core import HAPSession
        from repro.core.hap import fixed_plan
        from repro.models import init_params
        from repro.serving import Request

        cfg = dataclasses.replace(get_config('deepseek-moe-16b').reduced(),
                                  dtype='float32', capacity_factor=8.0)
        mesh = jax.make_mesh((1, 2), ('data', 'model'))
        params = init_params(cfg, jax.random.PRNGKey(0))

        def run(k):
            session = HAPSession(cfg, 'a6000', 2,
                                 source=fixed_plan('TP1', 'EP2'),
                                 mesh=mesh, prompt_bucket=16, gen_bucket=8)
            eng = session.engine(params, cfg=cfg, max_batch=2,
                                 moe_pipeline=k)
            for p in ([1, 2, 3, 4, 5], list(range(2, 14))):
                eng.submit(Request(prompt=p, max_new_tokens=8))
            return [c.tokens for c in eng.run()]

        serial = run(1)
        assert all(len(t) == 8 for t in serial)
        for k in (2, 4):
            assert run(k) == serial, k
        print('OK')
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr
