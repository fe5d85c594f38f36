"""Request-span tracer: a deterministic event journal for the serving
stack.

Every event carries the three-clock stamp (orchestrator/batcher tick,
deterministic work-clock units, wall-clock ns) plus a kind and free-form
attributes. Emission is a dict append — no device sync, no allocation
beyond the event itself — so tracing is zero-interference by
construction: token streams and the work clock are bit-identical with a
tracer attached or not (gated in ``benchmarks/serving.py``).

Event kinds, by scope:

* batcher scope (``island`` set by ``attach_tracer``): ``queue``,
  ``thaw_queue``, ``admit``, ``prefill`` (one per chunk-run dispatch,
  ``tokens`` = work dispatched), ``first_token``, ``decode`` (one per
  fused decode dispatch, ``rids`` = slots that advanced one token),
  ``preempt``, ``freeze``, ``finish``, ``exec_reject``, ``expire``
  (work-clock SLO budget blown mid-execution), and the KV-pool
  events ``page_alloc`` / ``page_cow`` / ``page_share``;
* orchestrator scope (``island=None``): ``submit``, ``route_tick``
  (per-island TIDE capacity snapshot), ``route`` (chosen island +
  score), ``dispatch`` / ``dispatch_sim``, ``migrate_out`` /
  ``migrate_in`` / ``migrate_return``, ``failover``, ``restart``,
  ``complete``, ``reject``, ``expire``, ``clock_anchor``;
* either scope: ``span`` — one named stretch of host work (``name``,
  ``dur_ns``; the event's ``wall_ns`` is its start), from the fixed
  vocabulary ``SPAN_NAMES``. Each span is also a
  ``jax.profiler.TraceAnnotation``, so a profiler trace shows it on the
  host plane next to the device ops. Without a tracer a span site is one
  attribute check and the shared no-op context ``NO_SPAN``: no
  annotation is built.

**One clock with the profiler.** ``wall_ns`` is ``perf_counter_ns``; the
profiler stamps host events on its own clock. ``clock_anchor()``, called
once a profiler trace has started, journals a stamp and writes a
zero-length ``obs.clock_anchor`` annotation right after it;
``trace_offset`` then maps any journal stamp onto the trace's clock.

**Trust boundary.** The raw event stream is operator-view only — the
same boundary as the Lighthouse's ``viewer_tier=None`` telemetry: it
names islands, requests and per-request work, all of which the scoped
tenant view deliberately withholds. The ONLY tenant-visible projection
is ``tenant_summary``, which reduces the journal to mesh-wide aggregate
counts over tiers the viewer may see and pushes every value through the
mesh ``TelemetryPolicy`` hardening (``lighthouse.harden_value``).

Self-validation (the CI gates ride these):

* ``work_by_island`` — per-request dispatched work, per island; its sum
  must equal each batcher's ``work_clock`` (span conservation: every
  work-clock unit is attributed to exactly one request);
* ``terminal_counts`` — orchestrator-level ``complete``/``reject``/
  ``expire`` events per rid; exactly one per submitted request, even
  across the drain/kill churn scenarios.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

from jax.profiler import TraceAnnotation


@dataclass
class TraceEvent:
    kind: str
    island: Optional[str]        # None = orchestrator scope
    rid: Optional[int]           # batcher-local or orchestrator rid
    tick: int                    # scheduling tick (scope-local clock)
    work: int                    # deterministic work clock at emission
    wall_ns: int                 # perf_counter_ns; profiling only
    seq: int                     # global emission order
    attrs: dict = field(default_factory=dict)


# orchestrator-scope kinds that resolve a request exactly once
TERMINAL_KINDS = ("complete", "reject", "expire")

# the span vocabulary, where each stretch of host work happens:
SPAN_NAMES = (
    "orch.tick",            # TickOrchestrator.tick, whole
    "orch.route",           # the routing pass over the pending pool
    "orch.route.mist",      # MIST sensitivity analysis of the pool
    "orch.route.kernel",    # pack, route_batch_tick, host read of its
                            # outputs (the host waits on the device here)
    "orch.dispatch",        # routed requests into island batchers
    "orch.feedback",        # TIDE/LIGHTHOUSE reports, counter roll-up
    "orch.complete",        # desanitize and build the Response
    "batcher.tick",         # one island batcher tick, whole
    "batcher.plan",         # admission, chunk planning, page tables
    "batcher.dispatch",     # launching one jitted step
    "batcher.materialize",  # device-to-host read of a finished stream
)
CLOCK_ANCHOR = "obs.clock_anchor"


class _NoSpan:
    """The shared context of every span site when no tracer is attached."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("tracer", "name", "island", "stamp", "ann", "ev")

    def __init__(self, tracer, name, island, stamp):
        self.tracer, self.name = tracer, name
        self.island, self.stamp = island, stamp

    def __enter__(self):
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.ev = self.tracer.emit("span", island=self.island,
                                   name=self.name, dur_ns=0, **self.stamp)
        return self

    def __exit__(self, *exc):
        self.ev.attrs["dur_ns"] = time.perf_counter_ns() - self.ev.wall_ns
        return self.ann.__exit__(*exc)


def trace_offset(anchor: dict, anchor_start_ns: float) -> float:
    """Nanoseconds to add to a journal stamp to place it on a profiler
    trace's clock: the trace's ``obs.clock_anchor`` host event starts
    ``anchor_start_ns``, right after the journal stamp ``anchor`` holds
    (``Tracer.clock_anchor``). The mapping holds whatever the trace
    counts from (``ProfileData`` counts from the profile's start)."""
    return anchor_start_ns - anchor["wall_ns"]


class Tracer:
    """Append-only event journal shared by one serving stack (an
    orchestrator plus its island batchers, or a standalone batcher)."""

    def __init__(self):
        self.events: list[TraceEvent] = []
        self._seq = 0

    def __len__(self):
        return len(self.events)

    def span(self, name: str, island: Optional[str] = None, *,
             tick: int = 0, work: int = 0):
        """Context manager for one named stretch of host work: a
        ``TraceAnnotation`` of ``name``, and a ``span`` event appended at
        entry (so the journal stays in start order) whose ``dur_ns`` is
        set at exit."""
        return _Span(self, name, island, {"tick": tick, "work": work})

    def clock_anchor(self) -> dict:
        """Pair the journal's clock with a running profiler trace's: a
        ``clock_anchor`` event whose ``wall_ns`` is read just before a
        zero-length ``obs.clock_anchor`` annotation opens, carrying the
        offset of the realtime clock (``offset_ns``, realtime less
        ``perf_counter_ns``). Returns the event's stamps (see
        ``trace_offset``)."""
        offset = time.time_ns() - time.perf_counter_ns()
        ann = TraceAnnotation(CLOCK_ANCHOR)
        wall = time.perf_counter_ns()
        with ann:
            pass
        self.emit("clock_anchor", wall_ns=wall, offset_ns=offset)
        return {"wall_ns": wall, "offset_ns": offset}

    def emit(self, kind: str, *, island: Optional[str] = None,
             rid: Optional[int] = None, tick: int = 0, work: int = 0,
             wall_ns: Optional[int] = None, **attrs):
        ev = TraceEvent(
            kind=kind, island=island, rid=rid, tick=int(tick),
            work=int(work),
            wall_ns=(time.perf_counter_ns() if wall_ns is None
                     else int(wall_ns)),
            seq=self._seq, attrs=attrs)
        self.events.append(ev)
        self._seq += 1
        return ev

    # ------------------------------------------------------- selection
    def by_kind(self, *kinds: str) -> list:
        want = set(kinds)
        return [e for e in self.events if e.kind in want]

    def islands(self) -> list:
        return sorted({e.island for e in self.events
                       if e.island is not None})

    # -------------------------------------------------- self-validation
    def work_by_island(self) -> dict:
        """{island: {rid: work}} — every dispatched work-clock unit,
        attributed to the request that consumed it: ``prefill`` events
        carry their token count, each rid in a ``decode`` event's row
        list advanced exactly one token."""
        out: dict = {}
        for e in self.events:
            if e.island is None:
                continue
            per = out.setdefault(e.island, {})
            if e.kind == "prefill" and e.rid is not None:
                per[e.rid] = per.get(e.rid, 0) + int(e.attrs["tokens"])
            elif e.kind == "decode":
                for rid in e.attrs.get("rids", ()):
                    per[rid] = per.get(rid, 0) + 1
        return out

    def conservation_ok(self, batchers: dict) -> dict:
        """Span conservation per island: the per-request work sums must
        reproduce each batcher's ``work_clock`` exactly. ``batchers``
        maps island id -> batcher (pass dead islands' batchers too —
        their journal stops where their clock froze, so the identity
        holds for them as well). Returns per-island booleans plus
        ``all``."""
        attributed = self.work_by_island()
        out = {}
        for iid, b in batchers.items():
            got = sum(attributed.get(iid, {}).values())
            out[iid] = (got == b.work_clock)
        out["all"] = all(out.values()) if out else True
        return out

    def terminal_counts(self) -> dict:
        """{rid: count} over orchestrator-scope terminal events."""
        counts: dict = {}
        for e in self.events:
            if e.island is None and e.kind in TERMINAL_KINDS \
                    and e.rid is not None:
                counts[e.rid] = counts.get(e.rid, 0) + 1
        return counts

    def terminals_exactly_once(self, rids) -> bool:
        """Every submitted rid resolved exactly once (no drops, no
        double completions) — the churn-scenario gate."""
        counts = self.terminal_counts()
        return all(counts.get(r, 0) == 1 for r in rids) \
            and all(r in set(rids) for r in counts)

    def first_token_counts(self) -> dict:
        """{(island, rid): count} of ``first_token`` events — exactly
        one per request per batcher it reached pre-first-token (a thaw
        that already holds its token emits none)."""
        counts: dict = {}
        for e in self.events:
            if e.kind == "first_token":
                key = (e.island, e.rid)
                counts[key] = counts.get(key, 0) + 1
        return counts

    # ---------------------------------------------------- tenant view
    def tenant_summary(self, policy, viewer_tier: int) -> dict:
        """The ONLY tenant-visible projection of the journal: mesh-wide
        event counts over trust tiers the viewer may see (tier' >=
        viewer_tier, matching the lighthouse's scoped view), hardened
        through the mesh ``TelemetryPolicy`` (round-up quantum +
        value-keyed noise). No islands, no rids, no clocks, no work —
        cumulative work deltas re-expose per-request timing even when
        aggregated, so they never cross this boundary. ``span`` and
        ``clock_anchor`` events (host timing) are never counted."""
        from repro.core.lighthouse import harden_value

        def visible(e):
            t = e.attrs.get("tier")
            return isinstance(t, int) and t >= viewer_tier

        counts = {"requests_completed": 0, "pages_allocated": 0}
        for e in self.events:
            if e.kind == "finish" and visible(e):
                counts["requests_completed"] += 1
            elif e.kind == "page_alloc" and visible(e):
                counts["pages_allocated"] += 1
        q = policy.quantum_pages
        return {"viewer_tier": viewer_tier,
                **{k: harden_value(policy, f"trace_{k}", v, q, viewer_tier)
                   for k, v in counts.items()}}
