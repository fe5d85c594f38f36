"""The journal's stages and numbers on a hand-made journal: each request's
stages sum to its latency, the means are the window's, the program's host
time per orchestrator tick leaves the batcher ticks out, and a window with
no requests or spans reads nothing."""
import pytest

import journal
from repro.obs import Tracer

MS = 1_000_000


def request(tr, rid, brid, t, island="isl"):
    """One request's journal at offsets (ms) from ``t`` (ms): submitted at
    t + 1, queued at + 3, admitted + 6, first token + 16, finished + 46."""
    tr.emit("submit", rid=rid, wall_ns=(t + 1) * MS)
    tr.emit("queue", island=island, rid=brid, wall_ns=(t + 3) * MS)
    ev = tr.emit("dispatch", rid=rid, wall_ns=(t + 3) * MS)
    ev.attrs.update(island=island, brid=brid)
    tr.emit("admit", island=island, rid=brid, wall_ns=(t + 6) * MS)
    tr.emit("first_token", island=island, rid=brid, wall_ns=(t + 16) * MS)
    tr.emit("finish", island=island, rid=brid, wall_ns=(t + 46) * MS)


def span(tr, name, t0_ms, dur_ms, island=None):
    ev = tr.emit("span", island=island, wall_ns=t0_ms * MS, name=name)
    ev.attrs["dur_ns"] = dur_ms * MS


def window():
    tr = Tracer()
    request(tr, 0, 0, 100)
    request(tr, 1, 1, 200)
    request(tr, 2, 0, 300, island="other")   # same batcher rid, other island
    # due 0.5 ms before submit; answered 4 ms after finish
    sent = {i: (i, (t + 0.5) / 1000.0, (t + 1) / 1000.0)
            for i, t in enumerate((100, 200, 300))}
    done = {i: ((t + 50) / 1000.0, object())
            for i, t in enumerate((100, 200, 300))}
    span(tr, "orch.tick", 100, 40)
    span(tr, "batcher.tick", 105, 10, island="isl")
    span(tr, "batcher.tick", 120, 15, island="isl")
    span(tr, "orch.route", 101, 2)
    span(tr, "orch.tick", 200, 20)
    span(tr, "orch.route", 201, 6)
    span(tr, "orch.route", 900, 50)                 # past the window
    return tr, sent, done


def test_stages_sum_to_latency():
    tr, sent, done = window()
    rows = journal.stages(tr.events, sent, done)
    assert sorted(rows) == [0, 1, 2]
    want = {"arrival_lag": 0.5, "orch_wait": 2.0, "slot_wait": 3.0,
            "prefill": 10.0, "decode": 30.0, "residual": 4.0}
    for r in rows.values():
        assert r == pytest.approx(want)
        assert sum(r.values()) == pytest.approx(49.5)


def test_window_metrics():
    tr, sent, done = window()
    m = journal.metrics(tr.events, sent, done, 0, 500 * MS)
    assert m == pytest.approx({"req_orch_wait_ms": 2.0,
                               "req_slot_wait_ms": 3.0,
                               "req_prefill_ms": 10.0,
                               "req_decode_ms": 30.0,
                               "route_host_ms_per_pass": 4.0})
    table = journal.span_table(tr.events, 0, 500 * MS)
    assert table["orch.tick"] == pytest.approx((2, 60.0, 30.0))
    assert table["orch.route"][0] == 2


def test_program_host_time_per_tick():
    tr, _sent, _done = window()
    # (40 - 10 - 15) and (20 - 0)
    assert journal.orch_host_ms_per_tick(tr.events, 0, 500 * MS) == \
        pytest.approx(17.5)


def test_a_pause_moves_to_its_own_stage():
    tr, sent, done = window()
    # the host held 120-230 ms: request 0 (due 100.5) finished at 146 ms
    # and was answered at 150; it decoded 116-146 and kept 4 ms residual
    rows = journal.stages(tr.events, sent, done, pause=(120 * MS, 230 * MS))
    assert rows[0]["decode"] == pytest.approx(4.0)
    assert rows[0]["pause"] == pytest.approx(30.0)
    # request 1 (due 200.5): everything up to 230 ms lies in the pause
    assert rows[1]["pause"] == pytest.approx(29.5)
    assert rows[1]["decode"] == pytest.approx(16.0)
    assert rows[2]["pause"] == 0
    for r in rows.values():
        assert sum(r.values()) == pytest.approx(49.5)
    m = journal.metrics(tr.events, sent, done, 0, 500 * MS,
                        pause=(120 * MS, 230 * MS))
    assert m["req_decode_ms"] == pytest.approx((4.0 + 16.0 + 30.0) / 3)


def test_unfinished_or_unjournaled_requests_are_left_out():
    tr, sent, done = window()
    del done[1]
    sent[7] = (7, 0.9, 0.9)                 # never journaled
    done[7] = (1.0, object())
    assert sorted(journal.stages(tr.events, sent, done)) == [0, 2]


def test_empty_window_reads_nothing():
    tr, sent, done = window()
    assert journal.metrics(tr.events, {}, {}, 600 * MS, 800 * MS) == {}
    assert journal.metrics(Tracer().events, sent, done, 0, 500 * MS) == {}
    assert journal.span_table(tr.events, 600 * MS, 800 * MS) == {}
    assert journal.orch_host_ms_per_tick(tr.events, 600 * MS,
                                         800 * MS) is None
