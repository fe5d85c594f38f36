"""Where a request's seconds go, read from the serving program's own
journal (``repro.obs.Tracer``, attached to the orchestrator).

Every stamp is ``perf_counter_ns``, the clock the benchmark's ``Feeder``
times requests on, so a request's latency (due time to its ``Response``
on the host) splits into six stages that sum to it exactly:

* ``arrival_lag`` — due time to the orchestrator's ``submit`` (how late
  the load generator ran);
* ``orch_wait`` — ``submit`` to the island batcher's ``queue``: the wait
  for the next routing pass, and the pass;
* ``slot_wait`` — ``queue`` to ``admit`` (a free decode slot and pages);
* ``prefill`` — ``admit`` to ``first_token``, the dispatch of the
  prompt's last chunk;
* ``decode`` — ``first_token`` to ``finish``, stamped after the sync
  that brings the answer to the host;
* ``residual`` — ``finish`` to the ``Feeder`` seeing the ``Response``
  (the rest of that orchestrator tick).

A preempted request keeps its first ``queue``, ``admit`` and
``first_token`` and its last ``finish``. Where the host was held for a
while by something outside the serving program (stopping the profiler
holds it 20-28 s on a TPU v5e), the part of each stage inside that
``pause`` moves to a seventh stage, ``pause``, so that the six stay what
the program did and the seven still sum to the latency. The means over a
window's requests are the numbers ``METRICS`` names; the span table and
the program's own host time per orchestrator tick read the ``span``
events.
"""
from __future__ import annotations

import statistics

STAGES = ("arrival_lag", "orch_wait", "slot_wait", "prefill", "decode",
          "residual")
LIFECYCLE = ("queue", "admit", "first_token", "finish")
# metric name -> the stage whose mean it is
METRICS = {"req_orch_wait_ms": "orch_wait", "req_slot_wait_ms": "slot_wait",
           "req_prefill_ms": "prefill", "req_decode_ms": "decode"}


def request_stamps(events) -> dict:
    """{orchestrator rid: {"submit", "queue", "admit", "first_token",
    "finish": wall_ns}} for every request dispatched to an island
    batcher; a stamp the journal lacks is left out."""
    submit, placed, island = {}, {}, {}
    for e in events:
        if e.island is None:
            if e.kind == "submit":
                submit[e.rid] = e.wall_ns
            elif e.kind == "dispatch":
                placed[e.rid] = (e.attrs["island"], e.attrs["brid"])
        elif e.kind in LIFECYCLE:
            rec = island.setdefault((e.island, e.rid), {})
            if e.kind == "finish" or e.kind not in rec:
                rec[e.kind] = e.wall_ns
    return {rid: dict(island.get(key, {}), submit=submit[rid])
            for rid, key in placed.items() if rid in submit}


def stages(events, sent: dict, done: dict, pause=None) -> dict:
    """{request idx: {stage: ms}} for each request the benchmark sent
    (``sent``: idx -> (rid, due s, sent s)) and saw answered (``done``:
    idx -> (answered s, Response)) whose every stamp is journaled; with
    ``pause`` (start_ns, end_ns), also the ``pause`` stage."""
    stamps = request_stamps(events)
    out = {}
    for idx, (rid, t_due, _sent) in sent.items():
        st, d = stamps.get(rid), done.get(idx)
        if st is None or d is None or d[1] is None \
                or any(k not in st for k in LIFECYCLE):
            continue
        pts = [t_due * 1e9, st["submit"], st["queue"], st["admit"],
               st["first_token"], st["finish"], d[0] * 1e9]
        row, held = {}, 0.0
        for name, a, b in zip(STAGES, pts, pts[1:]):
            lost = 0.0 if pause is None else \
                max(0.0, min(b, pause[1]) - max(a, pause[0]))
            row[name] = (b - a - lost) / 1e6
            held += lost
        if pause is not None:
            row["pause"] = held / 1e6
        out[idx] = row
    return out


def _spans(events, name, t0_ns, t1_ns):
    return [e for e in events if e.kind == "span"
            and e.attrs["name"] == name and t0_ns <= e.wall_ns < t1_ns]


def metrics(events, sent, done, t0_ns, t1_ns, pause=None) -> dict:
    """The window's numbers, each left out where nothing was read:
    ``METRICS``' stage means over the window's requests (``pause`` as in
    ``stages``), and ``route_host_ms_per_pass``, the mean ``orch.route``
    span that started in [t0_ns, t1_ns)."""
    rows = stages(events, sent, done, pause)
    out = {}
    if rows:
        for name, stage in METRICS.items():
            out[name] = statistics.fmean(r[stage] for r in rows.values())
    route = _spans(events, "orch.route", t0_ns, t1_ns)
    if route:
        out["route_host_ms_per_pass"] = statistics.fmean(
            e.attrs["dur_ns"] for e in route) / 1e6
    return out


def span_table(events, t0_ns, t1_ns) -> dict:
    """{span name: (count, total ms, mean ms)} over spans that started in
    [t0_ns, t1_ns)."""
    acc = {}
    for e in events:
        if e.kind == "span" and t0_ns <= e.wall_ns < t1_ns:
            n, tot = acc.get(e.attrs["name"], (0, 0))
            acc[e.attrs["name"]] = (n + 1, tot + e.attrs["dur_ns"])
    return {k: (n, tot / 1e6, tot / 1e6 / n)
            for k, (n, tot) in sorted(acc.items())}


def orch_host_ms_per_tick(events, t0_ns, t1_ns):
    """Mean over the ``orch.tick`` spans that started in [t0_ns, t1_ns)
    of each one's length less the ``batcher.tick`` spans inside it: the
    program's own reading of what ``orch_host_ms_per_tick`` times from
    outside."""
    ticks = _spans(events, "orch.tick", t0_ns, t1_ns)
    inner = sorted((e.wall_ns, e.attrs["dur_ns"]) for e in events
                   if e.kind == "span" and e.attrs["name"] == "batcher.tick")
    out, j = [], 0
    for t in ticks:
        end = t.wall_ns + t.attrs["dur_ns"]
        while j < len(inner) and inner[j][0] < t.wall_ns:
            j += 1
        k, busy = j, 0
        while k < len(inner) and inner[k][0] < end:
            busy += inner[k][1]
            k += 1
        out.append((t.attrs["dur_ns"] - busy) / 1e6)
    return statistics.fmean(out) if out else None
