"""Program spans and per-request records (``repro.serving.trace``).

A tiny paged engine serves a few requests on the CPU under
``jax.profiler``: each step leaves one ``engine.step`` span whose kind
matches the engine's counters, child spans nest inside their parents,
the spans reach the written xplane, and nothing records with the
profiler off. Request records order their event times and survive a
preemption. The jitted programs lower under their own names, with the
model's scopes in the compiled op metadata.
"""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import reduced
from repro.core import HAPSession
from repro.core.hap import fixed_plan
from repro.models import init_paged_cache, init_params
from repro.serving import Request, trace

PROMPTS = ([list(range(1, 13)), 6], [list(range(3, 12)), 6],
           [list(range(2, 22)), 6], [[5, 4, 3, 2, 1], 6])


@pytest.fixture(scope="module")
def moe_setup():
    cfg = reduced("deepseek-moe-16b", capacity_factor=8.0)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    session = HAPSession(cfg, "a6000", 1, source=fixed_plan("TP1", "TP1"),
                         prompt_bucket=16, gen_bucket=8)
    kw.setdefault("max_batch", 2)
    kw.setdefault("kv_block_size", 4)
    return session.engine(params, prefill_chunk=8, **kw)


@dataclasses.dataclass
class Served:
    engine: object
    completions: list
    spans: list
    on_during: bool
    on_after: bool
    xplane: str


@pytest.fixture(scope="module")
def served(moe_setup, tmp_path_factory):
    cfg, params = moe_setup
    eng = _engine(cfg, params)
    for p, g in PROMPTS:
        eng.submit(Request(prompt=p, max_new_tokens=g))
    d = str(tmp_path_factory.mktemp("xplane"))
    jax.profiler.start_trace(d)
    try:
        on_during = trace.profiling()
        comps = eng.serve_continuous()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    return Served(eng, comps, list(eng.trace.spans), on_during, trace.profiling(),
                  found[0] if found else "")


def _by_name(spans, name):
    return [s for s in spans if s.name == name]


def test_profiler_flag_follows_the_session(served):
    """The span switch is the profiler's own flag, the one private
    import of the module: on between start and stop, off after."""
    from jax._src.lib import _profiler

    assert trace.profiling is _profiler.TraceMe.is_enabled
    assert served.on_during is True
    assert served.on_after is False
    assert trace.profiling() is False


def test_one_step_span_per_step_with_the_counters_kinds(served):
    st = served.engine.stats
    kinds = [s.attrs["kind"] for s in _by_name(served.spans, "engine.step")]
    assert st.fused_steps >= 1  # the mix exercises every kind
    assert kinds.count("fused") == st.fused_steps
    assert kinds.count("chunk") == st.prefill_chunks - st.fused_steps
    assert kinds.count("decode") == st.decode_steps - st.fused_steps
    assert len(kinds) == st.decode_steps + st.prefill_chunks - st.fused_steps


@pytest.mark.parametrize("name", ["engine.blocks", "engine.inputs", "engine.dispatch",
                                  "engine.sync", "engine.book"])
def test_every_step_has_its_child_span(served, name):
    steps = {s.uid for s in _by_name(served.spans, "engine.step")}
    kids = [s.parent for s in _by_name(served.spans, name)]
    assert set(kids) == steps and len(kids) == len(steps)


def test_sample_spans_only_in_decode_carrying_steps(served):
    """A step samples where it yields tokens: every decode-carrying step,
    and the step of each request's final prompt chunk (its first token;
    a final chunk never fuses), so its wait is ``engine.sync``'s."""
    steps = _by_name(served.spans, "engine.step")
    last_chunk = {s.attrs["chunk_uid"]: s.uid for s in steps
                  if s.attrs["chunk_uid"] is not None}
    sampling = {s.uid for s in steps if s.attrs["kind"] in ("fused", "decode")}
    finals = set(last_chunk.values())
    assert all(s.attrs["kind"] == "chunk" for s in steps if s.uid in finals)
    parents = [s.parent for s in _by_name(served.spans, "engine.sample")]
    assert sorted(parents) == sorted(sampling | finals)


def test_children_lie_inside_their_parents(served):
    by_uid = {s.uid: s for s in served.spans}
    kids = [s for s in served.spans if s.parent is not None]
    assert kids
    for s in kids:
        p = by_uid[s.parent]
        assert p.start <= s.start <= s.end <= p.end, (s, p)


def test_join_and_plan_spans_nest_under_admit(served):
    by_uid = {s.uid: s for s in served.spans}
    joins = _by_name(served.spans, "engine.join")
    assert sorted(s.attrs["uid"] for s in joins) == [c.uid for c in served.completions]
    assert all(by_uid[s.parent].name == "engine.admit" for s in joins)
    plans = _by_name(served.spans, "engine.plan")
    assert plans and all(by_uid[s.parent].name == "engine.join" for s in plans)
    assert sum(s.attrs["joined"] for s in _by_name(served.spans, "engine.admit")) == 4


def test_step_attributes_describe_the_work(served):
    steps = _by_name(served.spans, "engine.step")
    for s in steps:
        a = s.attrs
        if a["kind"] == "decode":
            assert a["chunk_uid"] is None and a["rows"] >= 1 and a["ctx"] >= a["rows"]
        else:
            assert a["chunk_uid"] is not None and 0 <= a["chunk_real"] <= 8
    real = {}
    for s in steps:
        if s.attrs["chunk_uid"] is not None:
            uid = s.attrs["chunk_uid"]
            real[uid] = real.get(uid, 0) + s.attrs["chunk_real"]
    assert real == {uid: len(p) for uid, (p, _) in enumerate(PROMPTS)}


def test_decode_steps_count_the_pages_they_walk(served, moe_setup, tmp_path):
    """Decode-carrying steps carry the pages the decode kernel walks (each
    decoding row's up to its query position) against the rows' table
    width; prefill-only steps carry neither."""
    for s in _by_name(served.spans, "engine.step"):
        a = s.attrs
        if a["rows"]:
            assert a["rows"] <= a["kv_pages_live"] <= a["kv_pages_table"]
            assert a["kv_pages_table"] % a["rows"] == 0
        else:
            assert "kv_pages_live" not in a and "kv_pages_table" not in a
    # one row at known positions: a 12-token prompt padded to 16, then
    # decode writes and attends positions 16..20, 4 to a page
    cfg, params = moe_setup
    eng = _engine(cfg, params, max_batch=1)
    eng.submit(Request(prompt=list(range(1, 13)), max_new_tokens=6))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.serve_continuous()
    finally:
        jax.profiler.stop_trace()
    steps = [s.attrs for s in _by_name(eng.trace.spans, "engine.step")
             if s.attrs["kind"] == "decode"]
    assert [a["kv_pages_live"] for a in steps] == [p // 4 + 1 for p in range(16, 21)]
    widths = {a["kv_pages_table"] for a in steps}
    assert len(widths) == 1 and widths.pop() >= 6


def test_step_spans_reach_the_xplane(served):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(served.xplane)
    names = [e.name for plane in pd.planes for line in plane.lines for e in line.events]
    assert names.count("engine.step") == len(_by_name(served.spans, "engine.step"))
    assert "engine.dispatch" in names and "engine.sync" in names


def test_no_span_records_with_the_profiler_off(moe_setup):
    cfg, params = moe_setup
    eng = _engine(cfg, params)
    for p, g in PROMPTS[:2]:
        eng.submit(Request(prompt=p, max_new_tokens=g))
    comps = eng.serve_continuous()
    assert len(comps) == 2 and eng.stats.decode_steps > 0
    assert len(eng.trace.spans) == 0
    # request records are always on
    assert all(c.record is not None and c.record.finished is not None for c in comps)


def _ordered(rec):
    return (rec.submitted <= rec.joined <= rec.first_chunk
            <= rec.first_token <= rec.finished)


def test_request_records_order_their_events(served):
    assert len(served.completions) == len(PROMPTS)
    for c in served.completions:
        r = c.record
        assert r is served.engine.trace.get(c.uid) and r.uid == c.uid
        assert _ordered(r), r
        assert r.status == "ok" and r.preemptions == 0
        padded = 16 * -(-len(PROMPTS[c.uid][0]) // 16)
        assert r.chunks == padded // 8


def test_current_is_the_newest_engine(moe_setup, served):
    cfg, params = moe_setup
    eng = _engine(cfg, params)
    assert trace.current() is eng.trace
    uid = eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
    rec = trace.current().get(uid)
    assert rec.status == "queued" and rec.joined is None


def test_preempted_request_keeps_its_record(moe_setup):
    cfg, params = moe_setup
    reqs = ([list(range(1, 13)), 8], [list(range(3, 12)), 8], [[5, 4, 3, 2, 1], 8])
    eng = _engine(cfg, params, max_batch=3, kv_blocks=10, kv_overcommit=0.25)
    for p, g in reqs:
        eng.submit(Request(prompt=p, max_new_tokens=g))
    comps = eng.serve_continuous()
    assert eng.stats.preemptions >= 1
    hit = [c for c in comps if c.preemptions]
    assert hit
    for c in comps:
        assert _ordered(c.record) and c.record.preemptions == c.preemptions
    for c in hit:
        # the re-admission replays prompt + stashed tokens as more chunks
        assert c.record.chunks > 2 and c.record.uid == c.uid and c.record.status == "ok"


def test_finished_records_are_bounded(monkeypatch):
    monkeypatch.setattr(trace, "FINISHED_KEPT", 3)
    rec = trace.Recorder()
    for uid in range(6):
        rec.submitted(uid)
    for uid in range(5):
        rec.finished(uid, "ok")
    assert sorted(rec.requests) == [2, 3, 4, 5]  # the live one stays


@pytest.mark.parametrize("kind", ["prefill", "decode", "chunk", "fused", "cow"])
def test_jitted_programs_lower_under_their_names(moe_setup, kind):
    cfg, params = moe_setup
    eng = _engine(cfg, params)
    plan = eng._sharding_for("decode")
    cache = init_paged_cache(cfg, 2, 9, 4, 8, dtype=jnp.float32)
    tok = jnp.zeros((1, 8), jnp.int32)
    dec = jnp.zeros((2, 1), jnp.int32)
    lower = {
        "prefill": lambda: eng._prefill_fn(plan).lower(
            params, {"tokens": jnp.zeros((1, 16), jnp.int32)}, 32),
        "decode": lambda: eng._decode_fn(plan).lower(params, dec, cache),
        "chunk": lambda: eng._chunk_fn(plan).lower(params, tok, 0, cache),
        "fused": lambda: eng._fused_fn(plan).lower(params, tok, 0, dec, cache),
        "cow": lambda: eng._cow_fn().lower(cache.k, cache.v, jnp.zeros((1,), jnp.int32),
                                           jnp.ones((1,), jnp.int32)),
    }[kind]
    assert re.search(rf"module @jit_{kind}\b", lower().as_text())


def test_decode_ops_carry_the_model_scopes(moe_setup):
    cfg, params = moe_setup
    eng = _engine(cfg, params)
    cache = init_paged_cache(cfg, 2, 9, 4, 8, dtype=jnp.float32)
    hlo = eng._decode_fn(eng._sharding_for("decode")).lower(
        params, jnp.zeros((2, 1), jnp.int32), cache).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("attn", "kv_write", "router", "experts", "shared_experts", "head"):
        assert any(f"/{scope}/" in n for n in names), scope
    # the routed experts' per-layer weight slices sit under "experts"
    slices = [n for n in names if n.endswith("dynamic_slice") and "/experts/" in n]
    assert slices
