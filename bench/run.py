#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json`` per process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: published
widths, the deployment's islands, slots and context) and a traffic mix
(``bench/traffic/<mix>.json``, read by ``loadgen``). The run builds the
program's normal serving path from them — ``build_island_batchers`` and
``TickOrchestrator`` over the configuration's island registry, every
scheduling knob at the program's default — with weights drawn on the chip
from ``--seed``, warms up with a fixed stream, then drives the window:
open-loop arrivals at the traffic file's rate (each request timed from
its due time to its ``Response`` on the host), or a closed loop.

After the window it reads the device's peak memory, frees the program,
and checks what the window served against the plain reference
(``reference.py``): a sample of the finished requests, the longest among
them, is run through the float32 reference over its prompt and served
tokens, and the widest gap by which a served token's logit lies below the
reference's best must stay under the cell's limit (``bench/limits/
<cell>.json``). The guarantees are checked too: every request answered on
the chip's island, a clean page-pool audit, and in cells whose island
sanitizes, no identifying value of the prompt in what the island served.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
window under the profiler and prints its per-layer metrics, each read by
``bench/metrics/<name>.py``. The last line of standard output is one JSON
object. Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import counts  # noqa: E402
import loadgen  # noqa: E402

# JAX's persistent compilation cache: a fixed directory of the checkout,
# handed to the program through the variable it honours
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench" / "trace"
DRAIN_S = 60.0            # how long past the close an answer may come
SAMPLE_TOKENS = 384       # served tokens the correctness sample reaches
SAMPLE_MIN, SAMPLE_MAX = 3, 8
REF_ROWS = 16384          # sequence rows per reference pass
# an idle island's TIDE capacity is 1 - max(cpu 0.05, gpu 0, mem 0.10)
IDLE_CAPACITY = 0.895
IDLE_TICKS_MAX = 2000


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


# ------------------------------------------------------------------ stack


def program_config(cfg: dict):
    """The program's model configuration, set from the file's published
    keys (a width the program cannot take is an error, not a cut)."""
    import dataclasses

    from repro.configs.base import get_config
    base = get_config(cfg["program_arch"])
    pc = dataclasses.replace(
        base, num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab_size=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), qk_norm=True,
        attn_window=None, dtype=cfg["torch_dtype"])
    return pc


def build_registry(dep: dict):
    from repro.core.islands import (IslandRegistry, cloud_island,
                                    edge_island, personal_island)
    reg = IslandRegistry()
    for s in dep["islands"]:
        if s["kind"] == "personal":
            isl = personal_island(s["id"], latency_ms=s["latency_ms"],
                                  capacity_units=s["capacity_units"])
        elif s["kind"] == "edge":
            isl = edge_island(s["id"], privacy=s["privacy"], cost=s["cost"],
                              latency_ms=s["latency_ms"],
                              capacity_units=s["capacity_units"])
        else:
            isl = cloud_island(s["id"], privacy=s["privacy"], cost=s["cost"],
                               latency_ms=s["latency_ms"])
        reg.register(isl, reg.attestation_token(isl.island_id))
    return reg


def build_stack(cfg: dict, seed: int, device):
    """The serving path the deployment runs: its island registry behind
    WAVES (queue locally when nothing is feasible), one batcher per SHORE
    island, the tick orchestrator. Only ``max_len``, slots and the
    registry come from the configuration; every other knob is the
    program's default."""
    from repro.core.lighthouse import Lighthouse
    from repro.core.mist import MIST
    from repro.core.tide import TIDE
    from repro.core.waves import WAVES, Policy
    from repro.serving.engine import TickOrchestrator, build_island_batchers

    import weights
    dep = cfg["deployment"]
    reg = build_registry(dep)
    tide, lh = TIDE(reg), Lighthouse(reg)
    for isl in reg.all():
        lh.heartbeat(isl.island_id)
    waves = WAVES(MIST(), tide, lh, Policy(on_infeasible="queue_local"))
    params = weights.program_params(seed, counts.widths(cfg), device)
    batchers = build_island_batchers(
        program_config(cfg), reg, params=params, max_len=dep["max_len"],
        slots_per_capacity_unit=dep["slots_per_capacity_unit"], seed=seed)
    orch = TickOrchestrator(waves, reg, batchers, seed=seed)
    chip = [s["id"] for s in dep["islands"] if s.get("holds_chip")]
    return orch, chip


# ------------------------------------------------------------------ drive


class Feeder:
    """Feeds requests to the orchestrator and times each from its due
    time (open loop) or its send time (closed loop) to its ``Response``
    on the host."""

    def __init__(self, orch, chip=()):
        self.orch = orch
        self.sent = {}        # request idx -> (rid, t_due, t_sent)
        self.done = {}        # request idx -> (t_done, Response or None)
        self.waiting = {}     # request idx -> rid, not yet resolved
        self.ticks = 0
        # the chip's batchers, whose pages and decode slots the window's
        # use of the deployment is read from, up to ``close_at``
        self.batchers = [orch.batchers[i] for i in chip]
        self.close_at = float("inf")
        self.pages_peak = self.slots_peak = 0
        self.at_open = self.at_close = None

    def submit(self, r, t_due):
        from repro.core.waves import Request
        sens = {1: 0.9, 2: 0.6}[r.tier]
        rid = self.orch.submit(Request(query=r.prompt, priority=r.priority,
                                       user=r.tenant,
                                       sensitivity_override=sens),
                               max_new_tokens=r.max_new)
        self.sent[r.idx] = (rid, t_due, time.perf_counter())
        self.waiting[r.idx] = rid

    def use(self):
        """The chip's decode counters and pages in use, summed."""
        st = [b.stats for b in self.batchers]
        return {"decode_tokens": sum(x.get("decode_tokens", 0) for x in st),
                "decode_steps": sum(x.get("decode_steps", 0) for x in st),
                "pages": sum(b.pool.in_use() for b in self.batchers),
                "slots": sum(s.active for b in self.batchers for s in b.slots)}

    def tick(self):
        self.orch.tick()
        t1 = time.perf_counter()
        self.ticks += 1
        if self.batchers and self.at_close is None:
            u = self.use()
            self.pages_peak = max(self.pages_peak, u["pages"])
            self.slots_peak = max(self.slots_peak, u["slots"])
            if t1 >= self.close_at:
                self.at_close = u
        res = self.orch.results
        for idx, rid in list(self.waiting.items()):
            if rid in res:
                self.done[idx] = (t1, res[rid])
                del self.waiting[idx]

    def outstanding(self):
        return len(self.waiting)

    def open(self, t_open, close_s):
        self.close_at = t_open + close_s
        if self.batchers:
            self.at_open = self.use()
            self.pages_peak = self.at_open["pages"]
            self.slots_peak = self.at_open["slots"]

    def use_line(self):
        """The window's use of the deployment, read after each tick: the
        peaks of slots held (prefilling or decoding) and of pages in use,
        and the share of decode slots that held a sequence in the window's
        decode steps."""
        if self.at_open is None:
            return "no chip batchers"
        end = self.at_close or self.use()
        pool = sum(b.pool.num_pages - 1 for b in self.batchers)
        slots = sum(b.num_slots for b in self.batchers)
        steps = end["decode_steps"] - self.at_open["decode_steps"]
        toks = end["decode_tokens"] - self.at_open["decode_tokens"]
        occ = 100.0 * toks / (steps * slots) if steps else 0.0
        return (f"slots held peak {self.slots_peak} of {slots}; "
                f"pages in use peak {self.pages_peak} of {pool} "
                f"({100.0 * self.pages_peak / pool:.1f}%); decode slots "
                f"occupied {occ:.1f}% over {steps} decode steps")

    def run_open(self, reqs, t_open, close_s, drain=True):
        """Open loop: each request is sent at its due time (or as soon
        after as the loop comes round); after the close, tick until every
        request of the window has resolved or the drain limit passes."""
        i = 0
        self.open(t_open, close_s)
        while True:
            now = time.perf_counter() - t_open
            while i < len(reqs) and reqs[i].due <= now:
                self.submit(reqs[i], t_open + reqs[i].due)
                i += 1
            if i >= len(reqs) and (not drain or not self.outstanding()
                                   or now > close_s + DRAIN_S):
                return
            if self.orch.busy():
                self.tick()
            elif i < len(reqs):
                time.sleep(max(0.0, min(reqs[i].due - now, 0.05)))
            else:
                self.tick()

    def run_closed(self, reqs, t_open, close_s, outstanding, drain=False):
        """Closed loop: ``outstanding`` requests in flight, a new one sent
        whenever one resolves, until the close."""
        i = 0
        self.open(t_open, close_s)
        while True:
            now = time.perf_counter() - t_open
            while now < close_s and self.outstanding() < outstanding \
                    and i < len(reqs):
                t = time.perf_counter()
                self.submit(reqs[i], t)
                i += 1
            if now >= close_s and (not drain or not self.outstanding()
                                   or now > close_s + DRAIN_S):
                return
            if self.orch.busy():
                self.tick()
            else:
                time.sleep(0.001)


def warm_up(orch, traffic, seed, seconds):
    """The fixed warm-up (the same for every seed), so that the window
    starts on an idle island with every program it will run in place:

    1. the routing kernel at each pool size the window can hand it, with
       requests that no island may take (sensitivity above every
       island's privacy), refused in one tick each;
    2. the traffic file's warm-up requests, all sent at once, answers cut
       to ``warmup.max_new`` tokens, drained;
    3. one request whose context reaches the longest the window's
       requests can (longest prompt plus longest answer), last, so that
       the decode programs of every context the window reaches exist and
       were built shortest first, as a long-running island builds them;
    4. the host's read of a finished answer from the device-resident
       token buffer (an eager slice, compiled once per answer length),
       for each answer length of the window's requests (the same set for
       every seed);
    5. idle ticks until TIDE's load estimate of every island has decayed
       back to an idle island's (its clock runs on ticks, so load the
       warm-up left would otherwise refuse the window's first requests).
    """
    from repro.core.waves import Request
    wu = traffic["warmup"]
    for m in wu["route_pools"]:
        for _ in range(m):
            orch.submit(Request(query="warm-up", priority=traffic["priority"],
                                sensitivity_override=1.5), max_new_tokens=1)
        orch.tick()
    reqs, _ = loadgen.build(traffic, seed, 0, phase="warmup")
    d = Feeder(orch)
    t0 = time.perf_counter()
    for r in reqs:
        r.max_new = min(r.max_new, wu["max_new"])
        d.submit(r, t0)
    while d.outstanding():
        d.tick()
    reach = loadgen.reach_request(traffic, seed, wu["max_new"])
    d.submit(reach, time.perf_counter())
    while d.outstanding():
        d.tick()
    settle(orch)
    window, _ = loadgen.build(traffic, seed, seconds)
    warm_reads(orch, {r.max_new for r in window})
    return d


def settle(orch):
    """Tick an idle orchestrator until TIDE's load estimate of every island
    is back to an idle island's."""
    tide = orch.waves.tide
    for _ in range(IDLE_TICKS_MAX):
        if all(tide.peek_capacity(i.island_id) >= IDLE_CAPACITY
               for i in orch.registry.all()):
            break
        orch.tick()


def warm_reads(orch, lengths):
    """Compile the host's read of a finished answer of each length from
    every batcher's device-resident token buffer (the same eager slice
    the batcher makes when a request finishes)."""
    import jax.numpy as jnp
    import numpy as np
    for b in orch.batchers.values():
        buf = jnp.zeros((b.num_slots, b.max_len), jnp.int32, device=b.device)
        for n in sorted(lengths):
            np.asarray(buf[0, 0:n])


# ------------------------------------------------------------------ checks


def served_prompt(resp, req):
    """The prompt as the island served it: MIST's sanitized text where
    the route crossed a trust boundary, the request's own otherwise."""
    d = resp.decision
    if d is not None and d.sanitize and d.sanitized_history:
        return d.sanitized_history[-1]
    return req.prompt


def pick_sample(finished, seed):
    """The requests whose answers are compared: the one with the most
    served tokens, then others drawn from the seed, until the sample
    holds SAMPLE_TOKENS served tokens."""
    if not finished:
        return []
    longest = max(finished, key=lambda f: (len(f[1].tokens or ()), f[0].idx))
    rest = [f for f in finished if f is not longest]
    random.Random(seed ^ 0x5EED).shuffle(rest)
    out = [longest]
    n = len(longest[1].tokens or ())
    for f in rest:
        if len(out) >= SAMPLE_MAX or (n >= SAMPLE_TOKENS
                                      and len(out) >= SAMPLE_MIN):
            break
        out.append(f)
        n += len(f[1].tokens or ())
    return out


def reference_readings(cfg, seed, sample, control=False):
    """Per sampled request: the prompt as served plus its served tokens,
    through the reference. Returns the widest gap (reference best logit
    minus the reference logit of the served token) over all served
    tokens, and with ``control`` also the widest gap of the token the
    float8 control puts first."""
    import numpy as np

    import reference
    seqs, positions, toks = [], [], []
    for req, resp in sample:
        ids = loadgen.encode(served_prompt(resp, req))
        out = list(resp.tokens)
        seqs.append(ids + out[:-1])
        positions.append(list(range(len(ids) - 1, len(ids) - 1 + len(out))))
        toks.append([(t,) for t in out])
    # every run of a cell pads to the deployment's context, so the
    # reference's programs compile once per checkout
    T = max(max(len(s) for s in seqs), cfg["deployment"]["max_len"])
    T = -(-T // reference.ROWS) * reference.ROWS
    batch = max(1, min(4, REF_ROWS // T))
    res = {}
    ctrl = None
    if control:
        ctrl = reference.run(cfg, seed, seqs, positions, toks, fp8=True,
                             batch=batch, pad_to=T)
        toks = [[(t[0], int(c)) for t, c in zip(tk, cr[2])]
                for tk, cr in zip(toks, ctrl)]
    ref = reference.run(cfg, seed, seqs, positions, toks, batch=batch,
                        pad_to=T)
    gaps = [np.asarray(best) - np.asarray(picked)[:, 0]
            for best, picked, _ in ref]
    res["logit_gap"] = float(max(g.max() for g in gaps))
    res["tokens_compared"] = int(sum(len(g) for g in gaps))
    if control:
        cg = [np.asarray(best) - np.asarray(picked)[:, 1]
              for best, picked, _ in ref]
        res["control_gap"] = float(max(g.max() for g in cg))
    return res


def guarantee_checks(orch, chip, window, sanitizes):
    """Numbers that must be 0: answers from anywhere but the chip's
    island, a failed page-pool audit, identifying values of a prompt in
    what a sanitizing island served, answers of the wrong length."""
    off, short, leaks = 0, 0, 0
    for req, resp in window:
        if resp.island_id not in chip:
            off += 1
        if len(resp.tokens or ()) != req.max_new:
            short += 1
        if sanitizes:
            sp = served_prompt(resp, req)
            leaks += sum(1 for v in req.pii if v in sp)
            if not resp.sanitized:
                leaks += 1
    audit = 0
    for iid in chip:
        try:
            orch.batchers[iid].pool.audit()
        except AssertionError as e:
            log(f"page pool audit failed on {iid}: {e}")
            audit += 1
    out = {"off_island": off, "wrong_length": short, "pool_audit": audit}
    if sanitizes:
        out["pii_served"] = leaks
    return out


def compare(logit_gap, checks, limits):
    """Each number beside its limit, and whether every one is within it
    (a gap that was never read is not)."""
    compared = {"logit_gap": {"value": logit_gap,
                              "limit": limits["logit_gap"]}}
    for k, v in checks.items():
        compared[k] = {"value": v, "limit": 0}
    correct = logit_gap is not None and all(
        c["value"] <= c["limit"] for c in compared.values())
    return compared, correct


def cell_limits(cell: str) -> dict:
    path = BENCH / "limits" / f"{cell}.json"
    return load_json(path)


# ------------------------------------------------------------------ metrics


def latency_metrics(window, drv):
    import statistics
    lat = []
    for req, _resp in window:
        _rid, t_due, _ = drv.sent[req.idx]
        lat.append(1000.0 * (drv.done[req.idx][0] - t_due))
    lat.sort()
    q = statistics.quantiles(lat, n=100, method="inclusive") \
        if len(lat) > 1 else lat * 99
    return {"latency_p50_ms": statistics.median(lat),
            "latency_p95_ms": q[94]}


def load_reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ main


def setup_cache():
    """JAX's persistent compilation cache in the checkout's fixed
    directory, handed to the program through the variable it honours,
    every program cached however small."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the checkout's own cache never evicts (an evicting cache loses
    # entries whose access stamp it cannot find, and then compiles again)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None, allow_cpu=False, config_override=None,
         traffic_override=None, limits_override=None):
    """The command line; the keyword arguments let the CPU tests run the
    harness past its look for a chip, at a small size."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    cfg = load_json(BENCH / "configs" / f"{cell['config']}.json")
    if config_override:
        cfg = dict(cfg, **config_override)
    traffic = loadgen.load(cell["traffic"])
    if traffic_override:
        traffic = dict(traffic, **traffic_override)

    setup_cache()
    import jax
    import jax.monitoring

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not allow_cpu:
        log(f"no TPU: JAX found {dev.platform} devices; no result")
        return 2
    if len(devices) < cell["chips"]:
        log(f"the cell needs {cell['chips']} chips, JAX found "
            f"{len(devices)}; no result")
        return 2
    if dev.platform == "tpu":
        counts.peaks(dev.device_kind)          # unknown chip: an error
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; cell "
        f"{cell['name']}: {cfg['name']} under {traffic['name']}, seed "
        f"{args.seed}, {args.seconds:g} s, trace {args.trace}")

    compiles = {"backend": 0, "trace": 0}

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["backend"] += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            compiles["trace"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    orch, chip = build_stack(cfg, args.seed, dev)
    t_built = time.perf_counter()
    warm_up(orch, traffic, args.seed, args.seconds)
    t_warm = time.perf_counter()
    log(f"set-up: built in {t_built - T_START:.3f} s, warm-up stream "
        f"{t_warm - t_built:.3f} s, {compiles['backend']} compiles, "
        f"{compiles['trace']} traces")

    reqs, _corpus = loadgen.build(traffic, args.seed, args.seconds)
    c0 = dict(compiles)
    drv = Feeder(orch, chip)
    tracer = None
    if args.trace:
        import tracing
        tracer = tracing.Recorder(orch, chip, drv, TRACE_DIR)
    a = traffic["arrival"]
    t_open = time.perf_counter()
    setup_s = t_open - T_START
    if tracer is not None:
        tracer.start(args.seconds)
    if a["kind"] == "poisson":
        drv.run_open(reqs, t_open, args.seconds)
    else:
        drv.run_closed(reqs, t_open, args.seconds, a["outstanding"],
                       drain=bool(args.trace))
    t_close = t_open + args.seconds
    if tracer is not None:
        tracer.stop()
    in_window = {k: compiles[k] - c0[k] for k in compiles}
    late = [s - d for (_r, d, s) in drv.sent.values()]
    log(f"window: {len(drv.sent)} sent, {len(drv.done)} resolved, "
        f"{drv.ticks} ticks; compiles inside the window "
        f"{in_window['backend']} (traces {in_window['trace']}); generator "
        f"late by {1000 * sum(late) / max(len(late), 1):.3f} ms mean, "
        f"{1000 * max(late, default=0.0):.3f} ms max")
    log(f"window use: {drv.use_line()}")

    stats = dev.memory_stats() or {}
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:cell["chips"]])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem_peak)}
    log(f"memory: peak_bytes_in_use {mem_peak} of bytes_limit "
        f"{stats.get('bytes_limit')}")

    by_idx = {r.idx: r for r in reqs}
    attempted, failed, window = 0, 0, []
    closed = a["kind"] != "poisson"
    for idx, (rid, t_due, _ts) in drv.sent.items():
        done = drv.done.get(idx)
        if closed and (done is None or done[0] > t_close):
            continue                # a closed loop's tail is not due
        attempted += 1
        if done is None or done[1] is None:
            failed += 1
            continue
        window.append((by_idx[idx], done[1]))
    if tracer is not None:
        tracer.collect(reqs, served_prompt)
    sanitizes = any(s["kind"] == "edge" and s.get("holds_chip")
                    for s in cfg["deployment"]["islands"])
    checks = guarantee_checks(orch, chip, window, sanitizes)
    checks["unanswered"] = failed
    metrics_e2e = {}
    if window and not closed:
        metrics_e2e.update(latency_metrics(window, drv))
    if closed:
        toks = sum(len(resp.tokens or ()) for _q, resp in window)
        metrics_e2e["output_tokens_per_s"] = toks / args.seconds
    sample = pick_sample(window, args.seed)

    # free the program before the reference runs
    del orch, drv
    if tracer is not None:
        tracer.release()
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(cfg, args.seed, sample) if sample else \
        {"logit_gap": None, "tokens_compared": 0}
    log(f"reference: {len(sample)} requests, {ref['tokens_compared']} "
        f"served tokens compared in {time.perf_counter() - t_ref:.3f} s")

    limits = limits_override or cell_limits(cell["name"])
    compared, correct = compare(ref["logit_gap"], checks, limits)

    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        per = {}
        ctx = tracer.context(cfg, dev)
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            if m["source"] == "device_trace" and dev.platform != "tpu":
                continue          # a CPU rehearsal has no device numbers
            v = load_reader(m["name"]).read(ctx)
            if v is None:
                log(f"per-layer {m['name']}: nothing to read")
                continue
            if m["unit"] == "%" and v > 105.0:
                log(f"per-layer {m['name']} reads {v}%: a count is wrong")
                return 3
            per[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = per
        device.update(busy_s=ctx.trace["busy_s"],
                      window_s=ctx.trace["window_s"])
        result["breakdown"] = ctx.trace["breakdown"]
    else:
        e2e = {}
        for m in bench["end_to_end"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            v = setup_s if m["name"] == "setup_s" else \
                metrics_e2e.get(m["name"])
            if v is not None:
                e2e[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = e2e
    result["device"] = device
    result["checks"] = compared
    for k, c in compared.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
