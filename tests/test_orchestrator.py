"""Tick-based batched orchestrator: scalar-oracle parity, intra-tick
capacity safety, continuous-batching dispatch, end-to-end lifecycle."""
import numpy as np
import pytest

from repro.core import routing_jax as rj
from repro.core.islands import (IslandRegistry, cloud_island, edge_island,
                                personal_island)
from repro.core.lighthouse import Lighthouse
from repro.core.mist import MIST
from repro.core.tide import TIDE
from repro.core.waves import WAVES, Policy, Request
from repro.core.workload import healthcare_workload, legal_workload
from repro.serving.engine import TickOrchestrator


def fresh_stack(policy=None, islands=None):
    reg = IslandRegistry()
    for isl in islands or [
        personal_island("laptop", latency_ms=120, capacity_units=3.0),
        personal_island("phone", latency_ms=250, capacity_units=0.5),
        edge_island("home-nas", privacy=0.9, latency_ms=300),
        edge_island("clinic-edge", privacy=0.8, latency_ms=450,
                    datasets=("medlit",), capacity_units=6.0),
        cloud_island("gpt4-api", privacy=0.4, cost=0.02, latency_ms=900,
                     models=("gpt-4",)),
        cloud_island("claude-api", privacy=0.5, cost=0.015, latency_ms=800),
    ]:
        reg.register(isl, reg.attestation_token(isl.island_id))
    mist = MIST()
    tide = TIDE(reg)
    lh = Lighthouse(reg)
    for i in reg.all():
        lh.heartbeat(i.island_id)
    return reg, WAVES(mist, tide, lh, policy or Policy())


def decisions_key(ds):
    return [(d.accepted, d.island.island_id if d.accepted else None,
             d.reason) for d in ds]


# ------------------------------------------------- parity with the oracle

POLICIES = [
    ("scalarized", Policy()),
    ("constraint", Policy(mode="constraint")),
    ("queue_local", Policy(on_infeasible="queue_local", min_trust=0.9)),
    ("budget", Policy(budget_per_request=0.016)),
]


@pytest.mark.parametrize("name,policy", POLICIES)
def test_tick_router_matches_scalar_oracle(name, policy):
    """The batched tick pool is decision-equivalent to routing the same
    requests sequentially through scalar waves.route at a frozen clock."""
    wl = [r for r, _ in healthcare_workload(32, seed=3)]
    wl += [r for r, _ in legal_workload(16, seed=5)]
    _, wa = fresh_stack(policy)
    scalar = decisions_key([wa.route(r) for r in wl])
    regb, wb = fresh_stack(policy)
    orch = TickOrchestrator(wb, regb)
    batched = decisions_key(orch.route_pool(wl))
    assert batched == scalar


def test_tick_router_parity_with_special_constraints():
    """Deadline, dataset locality, model family, primary-tier and
    sensitivity-override requests all resolve like the oracle."""
    wl = [
        Request(query="summarize quarterly numbers", deadline_ms=200.0),
        Request(query="check medlit for trial outcomes", dataset="medlit"),
        Request(query="draft a note", model="gpt-4",
                sensitivity_override=0.1),
        Request(query="personal journal entry", priority="primary"),
        Request(query="weather tomorrow", priority="burstable"),
        Request(query="weather tomorrow again", priority="burstable"),
        Request(query="patient John Doe labs", priority="secondary"),
    ] * 3
    _, wa = fresh_stack()
    scalar = decisions_key([wa.route(r) for r in wl])
    regb, wb = fresh_stack()
    batched = decisions_key(TickOrchestrator(wb, regb).route_pool(wl))
    assert batched == scalar


def test_tick_router_crashed_tide_fails_closed():
    """A crashed TIDE must fail conservative (R=0, bounded islands reject
    secondary work) in the batched path exactly like the scalar oracle."""
    wl = [r for r, _ in healthcare_workload(16, seed=4)]
    rega, wa = fresh_stack()
    wa.tide.crashed = True
    scalar = decisions_key([wa.route(r) for r in wl])
    regb, wb = fresh_stack()
    wb.tide.crashed = True
    batched = decisions_key(TickOrchestrator(wb, regb).route_pool(wl))
    assert batched == scalar
    # nothing secondary/burstable lands on a bounded island
    for (acc, iid, _), r in zip(batched, wl):
        if acc and r.priority != "primary":
            assert regb.get(iid).unbounded


def test_tick_router_writes_tide_state_back():
    """After routing a pool, TIDE continues from the batch's load exactly
    like after the equivalent scalar sequence."""
    wl = [r for r, _ in healthcare_workload(20, seed=1)]
    rega, wa = fresh_stack()
    for r in wl:
        wa.route(r)
    regb, wb = fresh_stack()
    TickOrchestrator(wb, regb).route_pool(wl)
    for isl in rega.all():
        sa, sb = wa.tide._st(isl.island_id), wb.tide._st(isl.island_id)
        assert sa.local_ok == sb.local_ok
        for f in ("cpu", "gpu", "mem", "inflight"):
            assert getattr(sa, f) == pytest.approx(getattr(sb, f), abs=1e-5)


# -------------------------------------------- intra-tick capacity safety

def capacity_islands():
    return [
        personal_island("laptop", latency_ms=100, capacity_units=1.0),
        edge_island("edge-a", privacy=0.9, latency_ms=300,
                    capacity_units=2.0),
        cloud_island("cloud", privacy=0.9, cost=0.02, latency_ms=900),
    ]


def test_no_intra_tick_oversubscription():
    """Every in-tick assignment must have been admissible given the load of
    the assignments made before it — the exact gap in snapshot-based
    route_batch, which admits the whole pool against frozen capacity."""
    reqs = [Request(query=f"low sensitivity question {i}",
                    sensitivity_override=0.1) for i in range(12)]
    regb, wb = fresh_stack(islands=capacity_islands())
    ds = TickOrchestrator(wb, regb).route_pool(reqs)
    # replay sequentially against a fresh TIDE: each routed assignment must
    # be admitted at its turn, with only the earlier assignments' load
    reg2 = IslandRegistry()
    for isl in capacity_islands():
        reg2.register(isl, reg2.attestation_token(isl.island_id))
    tide2 = TIDE(reg2)
    for r, d in zip(reqs, ds):
        assert d.accepted
        if d.reason == "routed":
            assert tide2.admits(d.island.island_id, r.priority), \
                f"oversubscribed {d.island.island_id}"
            tide2.add_load(d.island.island_id, work=1.0)
    by = {}
    for d in ds:
        by[d.island.island_id] = by.get(d.island.island_id, 0) + 1
    # laptop (capacity_units=1) trips its secondary gate after ONE request
    assert by.get("laptop", 0) == 1
    # overflow lands on the unbounded island once bounded capacity is gone
    assert by.get("cloud", 0) >= 8


def test_snapshot_route_batch_oversubscribes_demo():
    """Documents the gap the tick router closes: the one-shot kernel sends
    the whole pool to the island that looked free at the snapshot."""
    regb, wb = fresh_stack(islands=capacity_islands())
    islands = wb.lighthouse.get_islands()
    tbl = rj.pack_islands(islands, [], wb.tide)
    m = 12
    reqs = rj.pack_requests(np.full(m, 0.1, np.float32),
                            np.full(m, 0.5, np.float32))
    w = np.asarray([0.4, 0.3, 0.3], np.float32)
    assign, _, _ = rj.route_batch(tbl, reqs, w)
    assert (np.asarray(assign) == 0).all()      # all 12 on the laptop
    state = rj.pack_tide_state(islands, wb.tide)
    extra = np.ones((m, len(islands)), bool)
    a2, acc, _, _, _, _ = rj.route_batch_tick(tbl, reqs, w, state, extra)
    assert (np.asarray(a2) == 0).sum() == 1     # tick router: exactly one


# --------------------------------------------------- end-to-end lifecycle

def test_orchestrator_end_to_end_with_batcher():
    from repro.configs.base import get_config
    from repro.serving.batcher import ContinuousBatcher
    cfg = get_config("smollm-135m").reduced()
    regb, wb = fresh_stack()
    bat = ContinuousBatcher(cfg, num_slots=2, max_len=64)
    orch = TickOrchestrator(wb, regb, {"laptop": bat})
    wl = healthcare_workload(8, seed=11)
    rids = [orch.submit(r, max_new_tokens=3) for r, _ in wl]
    orch.run_until_done()
    assert all(rid in orch.results for rid in rids)
    assert len(orch.log) + len(orch.rejected) == len(rids)
    s = orch.stats()
    assert s["privacy_violations"] == 0
    assert s["route_calls"] >= 1
    # SHORE work actually went through the continuous batcher
    if any(r.island_id == "laptop" for r in orch.log):
        assert bat.stats["prefill_dispatches"] >= 1
        assert bat.stats["decode_steps"] >= 1


def test_orchestrator_desanitizes_horizon_batch():
    """MIST forward+backward across a batched tick: cloud echoes reference
    placeholders; completions surface the original entity, placeholder-free."""
    islands = [cloud_island("api", privacy=0.9, cost=0.01, latency_ms=500)]
    regb, wb = fresh_stack(islands=islands)
    orch = TickOrchestrator(wb, regb)
    reqs = [Request(query=f"Tell Jonathan Smithers about item {i}",
                    sensitivity_override=0.1) for i in range(4)]
    rids = [orch.submit(r, max_new_tokens=4) for r in reqs]
    orch.run_until_done()
    for rid in rids:
        resp = orch.results[rid]
        assert resp is not None
        assert resp.sanitized
        assert "[" not in resp.text            # no placeholder leaked
        assert "Jonathan" in resp.text         # original entity restored


def test_batched_decode_single_dispatch():
    """One vmapped decode dispatch advances every active slot."""
    from repro.configs.base import get_config
    from repro.serving.batcher import ContinuousBatcher
    cfg = get_config("smollm-135m").reduced()
    b = ContinuousBatcher(cfg, num_slots=4, max_len=64)
    for i in range(4):
        b.submit(f"request {i}", max_new_tokens=5)
    b.run_until_done()
    assert len(b.finished) == 4
    assert b.stats["decode_tokens"] == 4 * 4   # 4 slots x (max_new-1) steps
    # fused: 4 slots advance per dispatch, not one dispatch per slot-token
    assert b.stats["decode_steps"] == 4


def test_session_chat_through_orchestrator():
    from repro.configs.base import get_config
    from repro.serving.batcher import ContinuousBatcher
    from repro.serving.session import SessionManager
    cfg = get_config("smollm-135m").reduced()
    regb, wb = fresh_stack()
    orch = TickOrchestrator(
        wb, regb, {"laptop": ContinuousBatcher(cfg, num_slots=2,
                                               max_len=64)})
    sm = SessionManager(orch)
    r1 = sm.chat("s1", "hello there", max_new_tokens=3)
    r2 = sm.chat("s1", "and a follow up", max_new_tokens=3)
    assert r1 is not None and r2 is not None
    s = sm.get("s1")
    assert len(s.history) == 4                 # 2 turns x (query, reply)
    assert len(s.islands_visited) == 2
