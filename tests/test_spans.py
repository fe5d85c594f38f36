"""Named host spans and device scopes: the span primitive, the fixed
span vocabulary on a served run, the clock shared with a profiler trace,
the request lifecycle's order in the journal, and the named scopes the
fused decode step carries to the device.

The invariants pinned here:

* without a tracer every span site is the one shared no-op context and
  nothing is journaled; with one, spans nest and the journal keeps start
  order;
* a served run opens every name of ``SPAN_NAMES`` and no other, and
  computes the same answers as an untraced run;
* once ``clock_anchor`` maps a journal stamp onto the profiler's clock,
  an event emitted inside a span lies inside that span's host event;
* every completed request's stamps run submit <= queue <= admit <=
  first_token <= finish <= complete, and the orchestrator journals its
  ``dispatch`` right after the batcher's ``queue``.
"""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.core.islands import IslandRegistry, personal_island
from repro.core.lighthouse import Lighthouse
from repro.core.mist import MIST
from repro.core.tide import TIDE
from repro.core.waves import WAVES, Policy, Request
from repro.obs import Tracer, chrome_trace_events
from repro.obs.trace import CLOCK_ANCHOR, NO_SPAN, SPAN_NAMES, trace_offset
from repro.serving.engine import TickOrchestrator, build_island_batchers

PROMPTS = [("span tour: a shared head for the island " * 2 + "one", 0.9),
           ("span tour: a shared head for the island " * 2 + "two", 0.9),
           ("an unrelated question about invoices", 0.6),
           ("what is the capital of France", None)]


@pytest.fixture(scope="module")
def cfg():
    return get_config("smollm-135m").reduced()


@pytest.fixture(scope="module")
def params(cfg):
    from repro.models.model import get_model
    return get_model(cfg).init(jax.random.PRNGKey(0), "float32")


def _serve(cfg, params, tracer):
    reg = IslandRegistry()
    reg.register(personal_island("solo", latency_ms=20.0,
                                 capacity_units=2.0),
                 reg.attestation_token("solo"))
    tide, lh = TIDE(reg), Lighthouse(reg)
    lh.heartbeat("solo")
    waves = WAVES(MIST(), tide, lh, Policy(on_infeasible="queue_local"))
    bats = build_island_batchers(cfg, reg, cache="paged", max_len=96,
                                 params=params)
    orch = TickOrchestrator(waves, reg, bats, tracer=tracer)
    rids = [orch.submit(Request(query=q, priority="primary",
                                sensitivity_override=s), max_new_tokens=5)
            for q, s in PROMPTS]
    orch.run_until_done()
    return orch, rids


@pytest.fixture(scope="module")
def served(cfg, params):
    """One untraced and one traced run of the same requests."""
    tr = Tracer()
    return _serve(cfg, params, None), _serve(cfg, params, tr), tr


def _spans(tr):
    return [e for e in tr.events if e.kind == "span"]


# ------------------------------------------------------ span primitive

def test_no_tracer_is_the_shared_noop(served):
    (orch, _), _on, _tr = served
    b = orch.batchers["solo"]
    assert orch.tracer is None and b.tracer is None
    for name in SPAN_NAMES:
        site = (orch._span(name) if name.startswith("orch.")
                else b._span(name))
        assert site is NO_SPAN
    with NO_SPAN as s:
        assert s is NO_SPAN


def test_spans_nest_in_start_order():
    tr = Tracer()
    with tr.span("orch.tick", tick=3, work=7):
        with tr.span("batcher.tick", island="isl"):
            probe = tr.emit("probe")
    outer, inner = _spans(tr)
    assert [e.seq for e in (outer, inner, probe)] == [0, 1, 2]
    assert (outer.attrs["name"], inner.attrs["name"]) == \
        ("orch.tick", "batcher.tick")
    assert (outer.island, inner.island) == (None, "isl")
    assert (outer.tick, outer.work) == (3, 7)
    o0, o1 = outer.wall_ns, outer.wall_ns + outer.attrs["dur_ns"]
    i0, i1 = inner.wall_ns, inner.wall_ns + inner.attrs["dur_ns"]
    assert o0 <= i0 <= probe.wall_ns <= i1 <= o1
    assert inner.attrs["dur_ns"] > 0


# ------------------------------------------------------ served run

def test_served_run_opens_the_whole_vocabulary(served):
    _off, _on, tr = served
    names = {e.attrs["name"] for e in _spans(tr)}
    assert names == set(SPAN_NAMES)
    for e in _spans(tr):
        assert (e.island is None) == e.attrs["name"].startswith("orch.")
    # the feedback after the batcher ticks is one span per orchestrator tick
    ticks = [e for e in _spans(tr) if e.attrs["name"] == "orch.tick"]
    feedback = [e.wall_ns for e in _spans(tr)
                if e.attrs["name"] == "orch.feedback"]
    for t in ticks:
        t0, t1 = t.wall_ns, t.wall_ns + t.attrs["dur_ns"]
        assert sum(t0 <= s <= t1 for s in feedback) == 1


def test_spans_change_nothing_served(served):
    (off, off_rids), (on, on_rids), _tr = served
    assert [off.results[r].tokens for r in off_rids] == \
        [on.results[r].tokens for r in on_rids]
    assert off.batchers["solo"].work_clock == on.batchers["solo"].work_clock
    assert off.tick_stats["ticks"] == on.tick_stats["ticks"]


def test_lifecycle_order_of_every_request(served):
    _off, (orch, rids), tr = served
    orch_ev = {(e.kind, e.rid): e for e in tr.events if e.island is None
               and e.kind in ("submit", "dispatch", "complete")}
    bat_ev = {}
    for e in tr.events:
        if e.island is not None and e.kind != "span":
            bat_ev.setdefault((e.kind, e.island, e.rid), e)
    assert all(orch.results[r] is not None for r in rids)
    for rid in rids:
        disp = orch_ev[("dispatch", rid)]
        key = (disp.attrs["island"], disp.attrs["brid"])
        queue = bat_ev[("queue",) + key]
        stamps = [orch_ev[("submit", rid)], queue,
                  bat_ev[("admit",) + key], bat_ev[("first_token",) + key],
                  bat_ev[("finish",) + key], orch_ev[("complete", rid)]]
        walls = [e.wall_ns for e in stamps]
        assert walls == sorted(walls), rid
        assert [e.seq for e in stamps] == sorted(e.seq for e in stamps)
        # b.submit journals the queue before it returns; the dispatch
        # follows with nothing between
        assert disp.seq == queue.seq + 1


def test_profiler_charged_from_the_dispatch_spans(cfg, params):
    from repro.obs import DispatchProfiler
    from repro.serving.batcher import PagedContinuousBatcher
    b = PagedContinuousBatcher(cfg, params=params, num_slots=2, max_len=96)
    b.profiler = DispatchProfiler()
    for q, _s in PROMPTS:
        b.submit(q, max_new_tokens=4, trust_tier=1)
    b.run_until_done()
    rep = b.profiler.report()
    assert rep["dispatches"] == b.stats["device_dispatches"] > 0
    assert rep["dispatch_submit_ms"] > 0
    # no tracer: the profiler's charge wraps the shared no-op
    assert b.profiler.charge("batcher.plan") is NO_SPAN


def test_export_renders_spans_as_complete_events(served):
    _off, _on, tr = served
    evs = chrome_trace_events(tr)
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == len(_spans(tr))
    assert {e["name"] for e in xs} == set(SPAN_NAMES)
    assert all(e["dur"] >= 0 for e in xs)
    tracks = {(e["pid"], e["tid"]) for e in xs}
    named = {(e["pid"], e["tid"]) for e in evs
             if e["ph"] == "M" and e.get("args", {}).get("name")
             == "host spans"}
    assert tracks == named and len(tracks) == 2   # orchestrator, island


def test_tenant_summary_ignores_spans(served):
    from repro.core.lighthouse import TelemetryPolicy
    _off, _on, tr = served
    bare = Tracer()
    bare.events = [e for e in tr.events if e.kind != "span"]
    pol = TelemetryPolicy()
    assert tr.tenant_summary(pol, 1) == bare.tenant_summary(pol, 1)


# ------------------------------------------------ one clock with a trace

def test_journal_stamp_lands_inside_its_span_on_the_trace(tmp_path):
    from jax.profiler import ProfileData
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        anchor = tr.clock_anchor()
        for _ in range(3):
            with tr.span("orch.tick"):
                probe = tr.emit("probe")
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    host = [ev for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    anchors = [ev for ev in host if ev.name == CLOCK_ANCHOR]
    assert len(anchors) == 1
    off = trace_offset(anchor, anchors[0].start_ns)
    # the profiler's host clock is realtime, counted here from the
    # profile's start: the recorded realtime offset agrees within 1 ms
    start, = [v for plane in pd.planes for k, v in plane.stats
              if k == "profile_start_time"]
    assert abs(anchor["offset_ns"] - start - off) < 1e6
    last = max((ev for ev in host if ev.name == "orch.tick"),
               key=lambda ev: ev.start_ns)
    t = probe.wall_ns + off
    assert last.start_ns <= t <= last.start_ns + last.duration_ns


# ------------------------------------------------------ device scopes

def test_fused_decode_step_carries_the_scopes():
    from repro.models.model import get_model
    from repro.models.steps import make_fused_decode_step
    from repro.serving.kvpool import PagePool
    cfg = get_config("qwen3-4b").reduced()
    model = get_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pool = PagePool(model, 64, 16, 9, dtype=jnp.bfloat16)
    B, i32 = 2, jnp.int32
    lowered = jax.jit(make_fused_decode_step(model)).lower(
        params, pool.pages, jnp.zeros(B, i32), jnp.ones(B, bool),
        jnp.zeros(B, i32), jnp.zeros(B, i32), jnp.zeros((B, 4), i32),
        jnp.zeros(B, i32), jnp.zeros(B, i32), jnp.zeros((B, 64), i32))
    stacks = re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
    scopes = {part for st in stacks for part in st.split("/")}
    for scope in ("embed", "attn", "mlp", "lm_head", "emit", "page_write"):
        assert scope in scopes, scope
    assert any("attn/page_write/" in st for st in stacks)
