#!/usr/bin/env python3
"""Where a cell's seconds go, read from the serving program's own spans.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

One ``run.py --trace 1`` run of the cell, unchanged (the same window, the
profiler over its last seconds, the per-layer metrics and the check
against the reference, and ``run.py``'s result line), with a
``repro.obs.Tracer`` attached to the orchestrator the run builds and its
clock anchored to the profiler's when the trace starts. Then, on
standard error:

* the span table of the window: count, total and mean ms per span name;
* each stage of the three slowest requests (the ones a 95th percentile
  over some forty requests rests on): arrival lag, orchestrator wait,
  slot wait, prefill, decode and residual (``journal.py``), the part of
  them spent while the profiler stopped (``pause``: the host is held from
  the window's close until the trace has stopped, and the requests still
  in flight wait with it), and their sum beside the latency the benchmark
  measured;

and a last line of JSON on standard output: the window's journal numbers
(``journal.metrics``, the profiler's stop left out), the program's own
host ms per orchestrator tick (``orch.tick`` less ``batcher.tick``)
beside the benchmark's outside timing of it, the widest gap between a
request's stages and its measured latency and the count of negative
stages (both 0 when the journal is sound), the slowest requests' stages,
and the trace reduced by the program's names (``trace_scopes``: device
seconds by named scope, idle gaps by innermost span), with how far the
clock anchor places the journal's spans from the profiler's own record
of them.
"""
from __future__ import annotations

import contextlib
import json
import sys
from bisect import bisect_left
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import journal  # noqa: E402
import run  # noqa: E402
import trace_scopes  # noqa: E402
import trace_reduce  # noqa: E402
import tracing  # noqa: E402

SLOWEST = 3


def log(msg):
    print(msg, file=sys.stderr, flush=True)


@contextlib.contextmanager
def attached(tracer, recorded=None):
    """``run.main`` with ``tracer`` attached to the orchestrator it
    builds; with ``recorded`` (a dict), a ``--trace 1`` run also anchors
    the tracer's clock when the profiler starts and leaves its
    ``tracing.Recorder`` there, with the window's requests, under
    ``"recorder"``, and the per-layer readers' context under
    ``"context"``."""
    build, recorder = run.build_stack, tracing.Recorder

    def build_stack(cfg, seed, device):
        orch, chip = build(cfg, seed, device)
        orch.attach_tracer(tracer)
        return orch, chip

    class Recorder(recorder):
        def _trace_on(self):
            super()._trace_on()
            self.anchor = tracer.clock_anchor()

        def release(self):
            self.sent, self.done = dict(self.drv.sent), dict(self.drv.done)
            super().release()

        def context(self, cfg, dev):
            recorded["recorder"] = self
            recorded["context"] = super().context(cfg, dev)
            return recorded["context"]

    run.build_stack = build_stack
    if recorded is not None:
        tracing.Recorder = Recorder
    try:
        yield
    finally:
        run.build_stack, tracing.Recorder = build, recorder


def anchor_error_ns(events, anchor, anchor_start, host):
    """Widest distance between an ``orch.tick`` span of the journal placed
    on the trace by the anchor and the profiler's own record of it (the
    nearest ``orch.tick`` host event), over the ticks the trace holds."""
    from repro.obs.trace import trace_offset
    starts = sorted(s for n, s, _e in host if n == "orch.tick")
    if anchor is None or anchor_start is None or not starts:
        return None
    off = trace_offset(anchor, anchor_start)
    worst = None
    for e in events:
        if e.kind != "span" or e.attrs["name"] != "orch.tick":
            continue
        t = e.wall_ns + off
        if not starts[0] <= t <= starts[-1]:
            continue
        i = bisect_left(starts, t)
        d = min(abs(t - starts[j]) for j in (i - 1, i)
                if 0 <= j < len(starts))
        worst = d if worst is None else max(worst, d)
    return worst


def report(tracer, rec, ctx):
    """The lines and numbers of the module docstring, from the journal,
    the recorder and the readers' context of one ``--trace 1`` run."""
    ev = tracer.events
    t0, t1, t2 = (int(rec.snaps[k][0] * 1e9)
                  for k in ("open", "close", "trace_end"))
    # the host held from the close until the trace has stopped
    pause = (t1, t2)
    out = {"journal": journal.metrics(ev, rec.sent, rec.done, t0, t1,
                                      pause),
           "pause_ms": (t2 - t1) / 1e6}
    log("spans in the window (count, total ms, mean ms):")
    for name, (n, tot, mean) in journal.span_table(ev, t0, t1).items():
        log(f"  {name:20s} {n:6d} {tot:12.3f} {mean:10.4f}")
    rows = journal.stages(ev, rec.sent, rec.done, pause)
    lat = {i: 1000.0 * (rec.done[i][0] - rec.sent[i][1]) for i in rows}
    out["stage_sum_gap_ms"] = max(
        (abs(sum(r.values()) - lat[i]) for i, r in rows.items()),
        default=None)
    out["negative_stages"] = sum(v < 0 for r in rows.values()
                                 for v in r.values())
    log(f"the {SLOWEST} slowest requests, stages in ms:")
    out["slowest"] = []
    for i in sorted(rows, key=lambda i: -lat[i])[:SLOWEST]:
        parts = " ".join(f"{k} {v:.3f}" for k, v in rows[i].items())
        log(f"  request {i}: {parts}; sum {sum(rows[i].values()):.3f}, "
            f"measured {lat[i]:.3f}")
        out["slowest"].append({"request": i, "latency_ms": lat[i],
                               "stages_ms": rows[i]})
    bench_ms = ctx.orch_host_ms
    out["orch_host_ms_per_tick"] = {
        "program": journal.orch_host_ms_per_tick(ev, t0, t1),
        "benchmark": sum(bench_ms) / len(bench_ms) if bench_ms else None}
    path = trace_reduce.newest_xplane(rec.dir)
    if path:
        tr, anchor_start, host = trace_scopes.reduce_file(path)
        if tr is not None:
            out["device"] = {"busy_s": tr["busy_s"],
                             "window_s": tr["window_s"]}
            out["breakdown"] = tr["breakdown"]
        out["anchor_error_ns"] = anchor_error_ns(
            ev, getattr(rec, "anchor", None), anchor_start, host)
    return out


def main(argv=None, **kw):
    """``run.main``'s command line (``--trace`` is always 1); the keyword
    arguments pass through to it (the CPU tests' small size)."""
    from repro.obs import Tracer
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--trace" in argv:
        del argv[argv.index("--trace"):argv.index("--trace") + 2]
    tracer, recorded = Tracer(), {}
    with attached(tracer, recorded):
        rc = run.main(argv + ["--trace", "1"], **kw)
    if rc != 0 or "recorder" not in recorded:
        return rc or 3
    print(json.dumps(report(tracer, recorded["recorder"],
                            recorded["context"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
