"""What a ``--trace 1`` run records besides the profiler's trace.

The run is the timed run's twin; the profiler covers the last
``TRACE_S`` seconds of the window, with the device drained at both ends
so that the trace holds exactly the steps dispatched between them. The
window's counters are read at its close, before the profiler stops:
stopping it holds the host for seconds (on a TPU v5e, long enough to
make every later arrival of a mid-window trace tens of seconds late), so
it comes after the last arrival and the window's counters. The recorder also times each
``TickOrchestrator.tick`` and each island batcher's ``tick`` inside it
(host clock, and a ``TraceAnnotation`` span for the trace), snapshots the
batchers' counters at the window's and the trace's ends, and after every
batcher tick notes which requests finished and how many decode steps the
batcher had run.

Work is counted from the requests, never from what a kernel read: every
decode step in the trace is attributed to the requests it advanced (a
request that finished after ``n`` answer tokens decoded in the ``n - 1``
steps that ended with the one it finished in; the run drains, so every
request the trace saw finishes), and the prompt tokens computed in the
trace carry the work per computed prompt token of the window's requests
(each request's computed positions run from the tokens the scheduler
skipped to the end of its prompt).
"""
from __future__ import annotations

import shutil
import time

import jax

import counts
import loadgen
import trace_reduce

TRACE_S = 6.0          # seconds under the profiler, ending at the close
COUNTERS = ("decode_tokens", "decode_steps", "prefill_chunk_tokens",
            "prefix_tokens_skipped")


class Context:
    """What the per-layer readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class Recorder:
    def __init__(self, orch, chip, drv, trace_dir):
        self.orch, self.drv, self.dir = orch, drv, trace_dir
        self.batchers = {iid: orch.batchers[iid] for iid in chip}
        self.slots = sum(b.num_slots for b in self.batchers.values())
        self.orch_ticks = []         # (t0, t1, batcher seconds inside)
        self.steps = []              # (island, decode_steps, finished rids)
        self.snaps = {}
        self._inside = 0.0
        self._plan = []
        self._orig = {}
        for iid, b in self.batchers.items():
            self._orig[iid] = b.tick
            b.tick = self._wrap_batcher(iid, b, b.tick)
        self._orig_orch = orch.tick
        orch.tick = self._wrap_orch(orch.tick)

    def _wrap_batcher(self, iid, b, fn):
        def tick():
            before = set(b.finished)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.batcher_tick"):
                fn()
            self._inside += time.perf_counter() - t0
            new = [r for r in b.finished if r not in before]
            self.steps.append((iid, b.stats.get("decode_steps", 0), new))
        return tick

    def _wrap_orch(self, fn):
        def tick():
            self._inside = 0.0
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.orch_tick"):
                out = fn()
            t1 = time.perf_counter()
            self.orch_ticks.append((t0, t1, self._inside))
            while self._plan and t1 >= self._plan[0][0]:
                self._plan.pop(0)[1]()
            return out
        return tick

    def _counters(self):
        out = {}
        for b in self.batchers.values():
            for k in COUNTERS:
                out[k] = out.get(k, 0) + b.stats.get(k, 0)
        return out

    def _snap(self, name):
        self.snaps[name] = (time.perf_counter(), self._counters())

    def _drain_device(self):
        for b in self.batchers.values():
            jax.block_until_ready(b.pool.pages)

    def _trace_on(self):
        self._drain_device()
        shutil.rmtree(self.dir, ignore_errors=True)
        self._snap("trace_start")
        jax.profiler.start_trace(str(self.dir))

    def _trace_off(self):
        self._drain_device()
        jax.profiler.stop_trace()
        self._snap("trace_end")

    def start(self, seconds):
        """Open the window; the trace starts on the first tick of its last
        ``TRACE_S`` seconds, and at the close the window's counters are
        read and then the trace stops."""
        t = time.perf_counter()
        self._snap("open")
        close = t + seconds
        self._plan = [(close - min(TRACE_S, seconds), self._trace_on),
                      (close, lambda: self._snap("close")),
                      (close, self._trace_off)]

    def stop(self):
        """After the run: whatever the plan had left happens now."""
        for _, step in self._plan:
            step()
        self._plan = []
        self._snap("end")

    def collect(self, reqs, served_prompt):
        """Per-request records of every request the run resolved:
        prompt tokens as served (``served_prompt(resp, req)``), answer
        tokens, prompt tokens the scheduler skipped, and the decode step
        it finished in."""
        by_tokens = {}
        for iid, b in self.batchers.items():
            for brid, rec in b.request_log.items():
                if rec.get("tokens") is not None:
                    by_tokens[id(rec["tokens"])] = (iid, brid, rec)
        finished_at = {}
        for iid, step, new in self.steps:
            for brid in new:
                finished_at[(iid, brid)] = step
        self.records = []
        by_idx = {r.idx: r for r in reqs}
        t_open = self.snaps["open"][0]
        for idx, (_t_done, resp) in self.drv.done.items():
            if resp is None or not resp.tokens or idx not in by_idx:
                continue
            hit = by_tokens.get(id(resp.tokens))
            rec = hit[2] if hit else {}
            self.records.append({
                "P": len(loadgen.encode(served_prompt(resp, by_idx[idx]))),
                "n": len(resp.tokens), "skipped": rec.get("tokens_skipped"),
                "end_step": finished_at.get(hit[:2]) if hit else None,
                "in_window": self.drv.sent[idx][2] >= t_open})

    def release(self):
        """Give the batchers and the orchestrator their own ``tick`` back
        and drop every reference to them, so the program can be freed."""
        for iid, b in self.batchers.items():
            b.tick = self._orig[iid]
        self.orch.tick = self._orig_orch
        self.orch = self.drv = self.batchers = None
        self._orig, self._orig_orch = {}, None

    # ------------------------------------------------------------------

    def _decode_work(self, lo, hi):
        """Tokens and summed contexts of the decode steps numbered
        (lo, hi], attributed to the requests that finished."""
        ctx_sum, toks = 0, 0
        for r in self.records:
            if r["end_step"] is None:
                continue
            first = r["end_step"] - (r["n"] - 2)
            for j in range(max(0, lo + 1 - first), r["n"] - 1):
                if first + j > hi:
                    break
                ctx_sum += r["P"] + j + 1
                toks += 1
        return ctx_sum, toks

    def context(self, cfg, dev):
        path = trace_reduce.newest_xplane(self.dir)
        tr = trace_reduce.reduce_file(path) if path else None
        if tr is None:
            tr = {"busy_s": 0.0, "window_s": 0.0, "modules": {},
                  "kernels": {}, "decode_steps": 0,
                  "breakdown": {"device_ops": [], "idle_gaps": []}}
        snap = {k: v[1] for k, v in self.snaps.items()}
        t_open, t_close = self.snaps["open"][0], self.snaps["close"][0]
        ts, te = snap["trace_start"], snap["trace_end"]
        # decode steps in the trace, by number, and their work
        dctx, dtok = self._decode_work(ts["decode_steps"], te["decode_steps"])
        # prompt work per computed prompt token of the window's requests
        win = [r for r in self.records if r["in_window"]]
        known = [r for r in win if r["skipped"] is not None]
        runs = [(r["skipped"], r["P"]) for r in known]
        computed = sum(b - a for a, b in runs)
        ptok = te["prefill_chunk_tokens"] - ts["prefill_chunk_tokens"]
        prefill = {"tokens": ptok, "ctx_sum": 0, "attn_flops": 0,
                   "attn_bytes": 0, "heads": 0,
                   "complete": bool(known) and len(known) == len(win)}
        if computed:
            fl, by = counts.prefill_attn_work(cfg, runs)
            share = ptok / computed
            prefill.update(
                ctx_sum=round(share * sum(counts.causal_ctx_sum(a, b)
                                          for a, b in runs)),
                attn_flops=share * fl, attn_bytes=share * by,
                heads=round(share * len(runs)))
        oc, cc = snap["open"], snap["close"]
        window = {
            "decode_tokens": cc["decode_tokens"] - oc["decode_tokens"],
            "decode_steps": cc["decode_steps"] - oc["decode_steps"],
            "prefix_skipped": snap["end"]["prefix_tokens_skipped"]
            - oc["prefix_tokens_skipped"],
            "prompt_tokens_sent": sum(r["P"] for r in win),
        }
        orch_ms = [1000.0 * (t1 - t0 - inner)
                   for t0, t1, inner in self.orch_ticks
                   if t_open <= t0 < t_close]
        return Context(cfg=cfg, kind=dev.device_kind, trace=tr,
                       window=window, slots=self.slots, orch_host_ms=orch_ms,
                       decode_traced={"ctx_sum": dctx, "tokens": dtok},
                       prefill_traced=prefill)
