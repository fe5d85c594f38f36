#!/usr/bin/env python3
"""Find a cell's knee once, by a sweep of fixed arrival rates on the chip.

    python3 bench/sweep.py --workload <cell> --rates 0.5,1,2,3 --seconds 20

One process builds the cell's stack and warms it up as ``run.py`` does,
then runs one open-loop window per rate (the traffic file's mix at that
rate, each window drained before the next) and prints, per rate, the
requests due and failed, the median and 95th-percentile latency, the
answer tokens per second, and the window's peak of pages in use and share
of decode slots occupied. The knee is the highest rate whose latency
stays flat and whose requests all succeed; the cell's traffic file then
fixes a rate at about four fifths of it. The benchmark's own runs never
sweep.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import loadgen  # noqa: E402
import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = run.load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = loadgen.load(cell["traffic"])
    run.setup_cache()
    import jax
    import jax.monitoring
    compiles = [0]

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 2
    orch, chip = run.build_stack(cfg, args.seed, dev)
    run.warm_up(orch, traffic, args.seed, args.seconds)
    windows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t = dict(traffic, arrival=dict(traffic["arrival"], rate_per_s=rate))
        windows.append((rate, loadgen.build(t, args.seed + i,
                                            args.seconds)[0]))
    run.warm_reads(orch, {r.max_new for _, reqs in windows for r in reqs})
    for rate, reqs in windows:
        run.settle(orch)
        c0 = compiles[0]
        drv = run.Feeder(orch, chip)
        t_open = time.perf_counter()
        drv.run_open(reqs, t_open, args.seconds)
        lat, toks, failed = [], 0, 0
        for r in reqs:
            done = drv.done.get(r.idx)
            if done is None or done[1] is None:
                failed += 1
                continue
            lat.append(1000.0 * (done[0] - drv.sent[r.idx][1]))
            toks += len(done[1].tokens or ())
        last = max((d[0] for d in drv.done.values()), default=t_open)
        q = statistics.quantiles(lat, n=100, method="inclusive") \
            if len(lat) > 1 else lat * 99
        print(json.dumps({
            "rate_per_s": rate, "due": len(reqs), "failed": failed,
            "latency_p50_ms": statistics.median(lat) if lat else None,
            "latency_p95_ms": q[94] if lat else None,
            "tokens_per_s": toks / max(last - t_open, 1e-9),
            "drain_s": last - t_open - args.seconds,
            "use": drv.use_line(), "compiles": compiles[0] - c0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
