"""Serving driver: ``python -m repro.launch.serve --requests 50``.

Boots a three-tier island mesh (personal laptop+phone, private edge, public
cloud), serves a real model on its SHORE islands, routes a healthcare
workload through WAVES and prints the per-island distribution, privacy
accounting and latency percentiles.

The model is ``--arch`` at its published widths, with random bfloat16
weights from ``--seed`` initialised once and shared by every island on a
device; ``--reduced`` serves the 2-layer smoke variant instead (the CPU
recipe). ``--batched`` swaps the per-request Algorithm-1 loop for the
tick-based batched orchestrator: the whole pending pool is routed per
scheduling tick through the capacity-aware ``route_batch_tick`` kernel and
SHORE work runs through per-island continuous batchers, island ``i`` on
device ``i % n`` (``serving.engine.build_island_batchers``).

``--trace out.json`` (implies ``--batched``) attaches the operator-side
span tracer (``repro.obs``) to the run and writes the request-span
journal as Chrome-trace/Perfetto JSON — islands as processes, decode
slots as tracks, migrations as flow arrows. Load it at ui.perfetto.dev
or chrome://tracing.
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import jax

from repro.configs.base import ARCH_IDS, get_config
from repro.core.islands import (IslandRegistry, cloud_island, edge_island,
                                personal_island)
from repro.core.lighthouse import Lighthouse
from repro.core.mist import MIST
from repro.core.tide import TIDE
from repro.core.waves import WAVES, Policy
from repro.core.workload import healthcare_workload
from repro.models.model import get_model
from repro.serving.engine import (InferenceEngine, LocalModelServer,
                                  TickOrchestrator, build_island_batchers)

# Context per request (prompt + generated). With one decode slot per unit
# of island capacity, the default mesh's four SHORE islands hold 12 slots
# of 2048 tokens: qwen3-4b's K/V at 147,456 B per token fills 3.6 GB of
# pages next to its 8.05 GB of bfloat16 weights, inside one 16 GB v5e
# chip with room for activations — and no pool can stall.
MAX_LEN = 2048
SLOTS_PER_CAPACITY_UNIT = 1.0

# Persistent compile cache used where JAX_COMPILATION_CACHE_DIR is unset:
# a fixed directory of the checkout (git-ignored), so every run of the
# same checkout finds what the last one compiled.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.
    A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and stands;
    otherwise the cache goes to ``CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def build_mesh(policy=None, buffer="moderate", classifier=None):
    reg = IslandRegistry()
    for isl in [
        personal_island("laptop", latency_ms=120, capacity_units=3.0,
                        models=("smoke",)),
        personal_island("phone", latency_ms=250, capacity_units=0.5),
        edge_island("home-nas", privacy=0.9, latency_ms=300,
                    capacity_units=2.0),
        edge_island("clinic-edge", privacy=0.8, latency_ms=450,
                    datasets=("medlit",), capacity_units=6.0),
        cloud_island("gpt4-api", privacy=0.4, cost=0.02, latency_ms=900),
        cloud_island("claude-api", privacy=0.5, cost=0.015, latency_ms=800),
    ]:
        reg.register(isl, reg.attestation_token(isl.island_id))
    mist = MIST(classifier=classifier)
    tide = TIDE(reg, buffer=buffer)
    lh = Lighthouse(reg)
    for i in reg.all():
        lh.heartbeat(i.island_id)
    waves = WAVES(mist, tide, lh, policy or Policy())
    return reg, waves


def build_orchestrator(cfg, registry, waves, *, params=None, seed=0,
                       cache="auto", prefill="chunked", tracer=None):
    """The batched serving stack over ``registry``'s SHORE islands: one
    continuous batcher per island (``MAX_LEN`` context, one decode slot
    per unit of capacity, pools that hold every slot), behind a
    ``TickOrchestrator``. ``params`` default to ``cfg``'s dtype from
    ``seed``."""
    if params is None:
        params = get_model(cfg).init(jax.random.PRNGKey(seed))
    batchers = build_island_batchers(
        cfg, registry, cache=cache, params=params, max_len=MAX_LEN,
        slots_per_capacity_unit=SLOTS_PER_CAPACITY_UNIT, seed=seed,
        prefill=prefill)
    return TickOrchestrator(waves, registry, batchers, seed=seed,
                            tracer=tracer)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the 2-layer smoke variant of --arch (the "
                         "CPU recipe) instead of its published widths")
    ap.add_argument("--requests", type=int, default=50)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--buffer", default="moderate",
                    choices=("conservative", "moderate", "aggressive"))
    ap.add_argument("--mode", default="scalarized",
                    choices=("scalarized", "constraint"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batched", action="store_true",
                    help="tick-based batched orchestrator instead of the "
                         "per-request loop")
    ap.add_argument("--cache", default="auto",
                    choices=("auto", "stacked", "paged"),
                    help="KV-cache manager for --batched SHORE islands: "
                         "dense stacked slot rows or the trust-tiered "
                         "paged pool; auto = paged when the arch supports "
                         "it (--batched only)")
    ap.add_argument("--prefill", default="chunked",
                    choices=("chunked", "full"),
                    help="paged-pool prefill policy: prefix-aware chunked "
                         "admission (skips shared-prefix FLOPs, budgeted "
                         "prefill/decode interleave) or the monolithic "
                         "full-prompt dispatch (--batched only)")
    ap.add_argument("--train-classifier", action="store_true",
                    help="train the MIST stage-2 JAX classifier first")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the request-span journal as Chrome-trace/"
                         "Perfetto JSON (implies --batched; operator-view "
                         "only)")
    args = ap.parse_args(argv)
    if args.trace and not args.batched:
        print("--trace implies --batched: enabling the tick orchestrator")
        args.batched = True

    clf = None
    if args.train_classifier:
        from repro.core.mist_model import train_classifier
        clf = train_classifier(seed=args.seed)
        print(f"MIST stage-2 classifier trained "
              f"(train acc {clf.train_accuracy:.3f})")

    setup_compile_cache()
    reg, waves = build_mesh(Policy(mode=args.mode), args.buffer, clf)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = get_model(cfg).init(jax.random.PRNGKey(args.seed))
    wl = healthcare_workload(args.requests, seed=args.seed)
    tracer = None
    if args.batched:
        if args.trace:
            from repro.obs import Tracer
            tracer = Tracer()
        eng = build_orchestrator(cfg, reg, waves, params=params,
                                 seed=args.seed, cache=args.cache,
                                 prefill=args.prefill, tracer=tracer)
    else:
        servers = {iid: LocalModelServer(cfg, params=params, max_len=MAX_LEN)
                   for iid in ("laptop", "home-nas")}
        eng = InferenceEngine(waves, reg, servers, seed=args.seed)
    for req, kind in wl:
        eng.submit(req, max_new_tokens=args.max_new_tokens)
    if args.batched:
        eng.run_until_done()
    print(json.dumps(eng.stats(), indent=1))
    if tracer is not None:
        from repro.obs import write_chrome_trace
        n = write_chrome_trace(tracer, args.trace)
        print(f"wrote {n} trace events to {args.trace} "
              f"(load at ui.perfetto.dev)")
    return eng


if __name__ == "__main__":
    main()
