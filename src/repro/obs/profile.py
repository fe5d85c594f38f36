"""Per-tick dispatch profiler: where does a serving tick's wall time go?

The open performance debt (``paged_ge_stacked_req_s: false`` in the
serving benchmark) is a boolean with no breakdown. This profiler splits
each batcher tick into phases so the host-plan vs device-execute split
is finally visible:

* ``host_plan``   — pure-Python scheduling: admission, chunk planning,
  page table updates, stream bookkeeping (derived: tick total minus the
  measured phases below);
* ``bucket``      — pow2 shape-bucket lookup/registration (cache misses
  here are recompiles);
* ``dispatch_submit`` — time spent *inside* the jitted calls as observed
  from the host: XLA argument staging + program launch + (on sync-heavy
  paths) device compute that the call itself blocks on. It is charged
  from the batcher's ``batcher.dispatch`` span sites (``charge``), one
  launch each, so the profiler and the tracer time the same calls;
* ``device_sync`` — the explicit ``block_until_ready`` tail the profiler
  issues at tick end so in-flight work is charged to the tick that
  launched it.

All numbers are wall-clock ns and therefore NEVER CI-gated — the
deterministic side of profiling is the shape/recompile counters, which
are exact. The profiler's end-of-tick sync changes when the host waits,
never what the device computes: token streams and the work clock are
unaffected (the tracing A/B gate runs with a profiler attached to pin
this down).

Usage::

    prof = DispatchProfiler()
    batcher.profiler = prof        # or orchestrator-wide via batchers
    ... run ticks ...
    prof.report()   # phase totals + fractions + per-tick p50/p95
"""
from __future__ import annotations

import time

from repro.obs.metrics import summarize
from repro.obs.trace import NO_SPAN


class DispatchProfiler:
    """Accumulates per-tick phase timings and dispatch-shape counters
    for one batcher. Attach one profiler per batcher — phase state is
    tick-scoped and not reentrant."""

    PHASES = ("host_plan", "bucket", "dispatch_submit", "device_sync")
    # span sites (repro.obs.trace) charged to a phase, one launch each
    SPAN_PHASES = {"batcher.dispatch": "dispatch_submit"}

    def __init__(self):
        self.ticks: list[dict] = []      # one record per profiled tick
        self.totals = {p: 0 for p in self.PHASES}
        self.total_ns = 0
        self.shape_counts: dict[tuple, int] = {}
        self.dispatches = 0
        self._cur: dict | None = None
        self._tick_t0 = 0

    # ---------------------------------------------------- tick framing
    def tick_begin(self):
        self._cur = {p: 0 for p in self.PHASES}
        self._cur["dispatches"] = 0
        self._tick_t0 = time.perf_counter_ns()

    def tick_end(self, sync_target=None):
        """Close the tick: optionally block on ``sync_target`` (charged
        to ``device_sync``) and fold the residual into ``host_plan``."""
        cur = self._cur
        if cur is None:
            return
        if sync_target is not None:
            import jax
            t0 = time.perf_counter_ns()
            jax.block_until_ready(sync_target)
            cur["device_sync"] += time.perf_counter_ns() - t0
        total = time.perf_counter_ns() - self._tick_t0
        measured = (cur["bucket"] + cur["dispatch_submit"]
                    + cur["device_sync"])
        cur["host_plan"] = max(total - measured, 0)
        cur["total"] = total
        for p in self.PHASES:
            self.totals[p] += cur[p]
        self.total_ns += total
        self.dispatches += cur["dispatches"]
        self.ticks.append(cur)
        self._cur = None

    # -------------------------------------------------- phase charging
    def phase(self, name: str):
        """Context manager charging its block to ``name`` in the current
        tick (no-op outside a tick, so jit wraps need no guards)."""
        return _Phase(self, name)

    def charge(self, span_name: str, inner=NO_SPAN):
        """The context of span site ``span_name``: ``inner`` (the tracer's
        span, if any), charged to the phase the site maps to as one
        launch; ``inner`` alone where it maps to none."""
        phase = self.SPAN_PHASES.get(span_name)
        if phase is None:
            return inner
        return _Phase(self, phase, inner, dispatches=1)

    def add_ns(self, name: str, ns: int, dispatches: int = 0):
        if self._cur is not None:
            self._cur[name] += ns
            self._cur["dispatches"] += dispatches

    def note_shapes(self, entries):
        """Record dispatch-shape tuples (from ``batcher.dispatch_shapes``
        slices). First sighting of a shape == one fresh XLA compile."""
        for s in entries:
            key = tuple(s)
            self.shape_counts[key] = self.shape_counts.get(key, 0) + 1

    # ----------------------------------------------------------- report
    def report(self) -> dict:
        """Phase totals (ms), fractions of profiled wall time, per-tick
        total p50/p95, and the deterministic shape counters."""
        out = {"ticks": len(self.ticks), "dispatches": self.dispatches,
               "total_ms": round(self.total_ns / 1e6, 3)}
        for p in self.PHASES:
            out[f"{p}_ms"] = round(self.totals[p] / 1e6, 3)
            out[f"{p}_frac"] = round(self.totals[p] / self.total_ns, 4) \
                if self.total_ns else 0.0
        out.update(summarize(
            [round(t["total"] / 1e6, 3) for t in self.ticks], "tick_ms"))
        out["unique_shapes"] = len(self.shape_counts)
        out["shape_dispatches"] = sum(self.shape_counts.values())
        return out


class _Phase:
    __slots__ = ("prof", "name", "inner", "dispatches", "t0")

    def __init__(self, prof, name, inner=NO_SPAN, dispatches=0):
        self.prof, self.name = prof, name
        self.inner, self.dispatches = inner, dispatches

    def __enter__(self):
        self.inner.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.prof.add_ns(self.name, time.perf_counter_ns() - self.t0,
                         self.dispatches)
        return self.inner.__exit__(*exc)
