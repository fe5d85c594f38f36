#!/usr/bin/env python3
"""Readings that set a cell's limit on the logit gap, in one process.

    python3 bench/control.py --workload <cell> --seeds 1-12 --control 1-3 \
        --seconds 10 [--fault-seeds 13-15 --fault state_unchanged]

For each seed it runs the cell as ``run.py`` does (same stack, same
warm-up, a window at the cell's own load), then compares the sampled
answers with the float32 reference: the program's reading is the widest
gap by which a served token's logit lies below the reference's best. For
the ``--control`` seeds it also runs the control, the reference computed
with float8 operands, over the same prompts and tokens: its reading is
the widest gap of the token the control puts first. The lower reading of
the limit is the largest of the program's, the upper the smallest of the
control's. The ``--fault-seeds`` run last, with a fault of ``faults.py``
planted under the timed path. Every reading goes through ``run.py``'s
comparison with the cell's limits file, so each line says whether that
run, or the control in the program's place, would be ``correct``. The
benchmark's own runs never run the control or a fault.

Prints one JSON line per seed and a summary, and with ``--out`` writes
them all to that file.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import faults  # noqa: E402
import run  # noqa: E402


def seeds(spec: str):
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def one_seed(cell, cfg, traffic, seed, seconds, dev, control):
    import loadgen
    orch, chip = run.build_stack(cfg, seed, dev)
    run.warm_up(orch, traffic, seed, seconds)
    reqs, _ = loadgen.build(traffic, seed, seconds)
    drv = run.Feeder(orch, chip)
    t_open = time.perf_counter()
    a = traffic["arrival"]
    if a["kind"] == "poisson":
        drv.run_open(reqs, t_open, seconds)
    else:
        drv.run_closed(reqs, t_open, seconds, a["outstanding"])
    by_idx = {r.idx: r for r in reqs}
    window = [(by_idx[i], d[1]) for i, d in drv.done.items()
              if d[1] is not None and (a["kind"] == "poisson"
                                       or d[0] <= t_open + seconds)]
    sanitizes = any(s["kind"] == "edge" and s.get("holds_chip")
                    for s in cfg["deployment"]["islands"])
    checks = run.guarantee_checks(orch, chip, window, sanitizes)
    checks["unanswered"] = len(reqs) - len(window) \
        if a["kind"] == "poisson" else 0
    sample = run.pick_sample(window, seed)
    use = drv.use_line()
    del orch, drv
    gc.collect()
    t0 = time.perf_counter()
    res = run.reference_readings(cfg, seed, sample, control=control)
    res.update(seed=seed, cell=cell, requests=len(window),
               sample=len(sample), checks=checks, use=use,
               reference_s=time.perf_counter() - t0)
    return res


def judged(res, limits, fault=None):
    """The run's numbers through ``run.py``'s comparison: whether the
    program (or, with a fault, the broken program) is ``correct``, and
    whether the control put in its place would be."""
    _c, res["correct"] = run.compare(res["logit_gap"], res["checks"], limits)
    if "control_gap" in res:
        _c, res["control_correct"] = run.compare(res["control_gap"],
                                                 res["checks"], limits)
    if fault:
        res["fault"] = fault
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--fault", default="state_unchanged",
                    choices=sorted(faults.FAULTS))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = run.load_json(BENCH / "configs" / f"{cell['config']}.json")
    import loadgen
    traffic = loadgen.load(cell["traffic"])
    run.setup_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 2
    limits = run.cell_limits(cell["name"])
    ctrl = set(seeds(args.control))
    out = []
    for s in seeds(args.seeds):
        r = judged(one_seed(cell["name"], cfg, traffic, s, args.seconds, dev,
                            control=s in ctrl), limits)
        out.append(r)
        print(json.dumps(r), flush=True)
    fault_seeds = seeds(args.fault_seeds)
    if fault_seeds:
        faults.FAULTS[args.fault](setattr)
    for s in fault_seeds:
        r = judged(one_seed(cell["name"], cfg, traffic, s, args.seconds, dev,
                            control=False), limits, fault=args.fault)
        out.append(r)
        print(json.dumps(r), flush=True)
    prog = [r["logit_gap"] for r in out if "fault" not in r]
    summary = {"cell": cell["name"], "limit": limits["logit_gap"]}
    if prog:
        summary.update(program_max=max(prog), program=prog)
    cg = [r["control_gap"] for r in out if "control_gap" in r]
    if cg:
        summary.update(control_min=min(cg), control=cg)
    fg = [r["logit_gap"] for r in out if "fault" in r]
    if fg:
        summary.update(fault=args.fault, fault_min=min(fg), faulted=fg)
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"seeds": out, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
