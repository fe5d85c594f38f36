#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: qwen3-4b at its published
widths, random bfloat16 weights from ``--seed``, driven through the
launcher's own stack — ``repro.launch.serve``'s ``build_mesh`` and
``build_orchestrator`` (``build_island_batchers`` + ``TickOrchestrator``)
→ paged chunked batchers → trust-tiered page pools → Pallas kernels.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four replica islands, one per chip

One chip, three phases:

1. serve — a seeded ``core.tracegen`` trace (prompts of a few hundred to
   ~1.5k tokens, a shared head on most) streams through the orchestrator,
   so chunked prefill, page sharing and fused decode all run. Every
   request must complete on a SHORE island with its requested number of
   tokens, none rejected, and every served decode's logits finite.
2. lowering — the fused decode and prefill steps the batchers compiled
   must contain the Pallas kernels (``tpu_custom_call``).
3. kernels — each paged kernel must agree with its ``kernels/ref.py``
   oracle at the served shapes, on the K/V the serve phase wrote.

``--chips 4`` runs only the replica phase: the same trace through four
SHORE islands behind WAVES, one per chip, and through one island on
device 0; each island's arrays must sit on its own device, and per-request
greedy streams must match apart from near-ties (counted and printed).

Earlier lines print what was run (device, compile seconds, requests,
tokens, wall seconds, peak device bytes); none of it is a speed claim.
The last line is ``{"ok": true, "device": {...}}``. Without a TPU, or
when any phase fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-4b"

# Kernel vs oracle: both read the same bfloat16 pages and accumulate in
# float32, so they differ by summation order (online vs one-shot softmax)
# and by the precision of the kernel's matmul passes — each at most about
# one bfloat16 ulp (2^-8 relative) of the output — plus the final rounding
# of the output to bfloat16. 2^-6 absolute plus 2^-6 relative is four such
# ulps at unit scale: loose enough for that, and far below what a wrong
# page, head, layer or mask moves an attention output of this scale.
KERNEL_TOL = 2.0 ** -6

# Replica streams: the two runs batch and chunk each request differently,
# so bfloat16 accumulation order differs through 36 layers. A differing
# greedy token is a near-tie when the top-2 logit gap at the first
# differing step is within NEAR_TIE_ULPS bfloat16 ulps of the top logit.
NEAR_TIE_ULPS = 4


def log(msg):
    print(msg, flush=True)


def trace_spec(n, seed):
    from repro.core.tracegen import (ArrivalSpec, LengthSpec, PrefixSpec,
                                     TraceSpec)
    # tiers 1-2 only: their sensitivity keeps every request on the
    # trusted SHORE islands, where the model runs (tier 3 may go to the
    # simulated cloud islands, which return text but no tokens)
    return TraceSpec(
        n_requests=n, seed=seed,
        arrivals=ArrivalSpec(base_rate=2.0, diurnal_period=0, burst_every=0),
        lengths=LengthSpec(prompt_min=256, prompt_max=1536, prompt_alpha=1.1,
                           output_min=8, output_max=32, output_alpha=1.4),
        prefix=PrefixSpec(corpus_size=4, head_tokens=256, zipf_s=1.1,
                          reuse_p=0.6),
        tiers=((1, 0.5), (2, 0.5)))


class StepSpy:
    """Wraps a batcher's jitted step: keeps the shapes of its first call
    (to lower it again later) and a device-side flag that every logits
    output it returned was finite — no host sync per call."""

    def __init__(self, fn, has_logits):
        import jax.numpy as jnp
        self.fn, self.has_logits = fn, has_logits
        self.shapes, self.calls = None, 0
        self.finite = jnp.bool_(True)

    def __call__(self, *args):
        import jax
        if self.shapes is None:
            self.shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                sharding=a.sharding)
                           if hasattr(a, "sharding") else a
                           for a in jax.tree.leaves(args)]
            self.treedef = jax.tree.structure(args)
        out = self.fn(*args)
        self.calls += 1
        if self.has_logits:
            import jax.numpy as jnp
            self.finite = self.finite & jnp.all(jnp.isfinite(out[0]))
        return out

    def lowered_text(self):
        import jax
        args = jax.tree.unflatten(self.treedef, self.shapes)
        return self.fn.lower(*args).as_text()


def check(cond, what):
    if not cond:
        raise AssertionError(what)
    log(f"  ok: {what}")


def serve_trace(orch, trace):
    from repro.core.tracegen import stream_trace
    t0 = time.perf_counter()
    rids = stream_trace(orch, trace)
    wall = time.perf_counter() - t0
    return rids, wall


def check_served(orch, trace, rids):
    """Every request completed on a SHORE island with its token count."""
    check(len(orch.rejected) == 0,
          f"no request rejected ({len(orch.rejected)} rejected)")
    missing = [r for r in rids if orch.results.get(r) is None]
    check(not missing, f"all {len(rids)} requests answered")
    short = [(tr.idx, len(orch.results[r].tokens or []), tr.max_new_tokens)
             for tr, r in zip(trace, rids)
             if len(orch.results[r].tokens or []) != tr.max_new_tokens]
    check(not short, f"every request got its requested tokens {short[:4]}")
    return sum(tr.max_new_tokens for tr in trace)


def phase_one_chip(args, dev):
    import jax
    import numpy as np
    from repro.configs.base import get_config
    from repro.core.tracegen import generate_trace
    from repro.core.waves import Policy
    from repro.launch import serve

    cfg = get_config(ARCH)
    log(f"phase serve: {ARCH} at published widths "
        f"({cfg.num_layers} layers, d {cfg.d_model}, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads x {cfg.head_dim}, "
        f"vocab {cfg.vocab_size}), max_len {serve.MAX_LEN}")
    # queue_local: a request that finds no island with spare capacity
    # waits at a trusted local island instead of failing closed, so the
    # whole trace is served (the fail-closed default would shed the burst)
    reg, waves = serve.build_mesh(Policy(on_infeasible="queue_local"))
    orch = serve.build_orchestrator(cfg, reg, waves, seed=args.seed)
    spies = {}
    for iid, b in orch.batchers.items():
        check(b.fused and b.prefill_mode == "chunked",
              f"{iid}: fused chunked-prefill paged batcher, "
              f"{b.num_slots} slots, {b.pool.num_pages} pages")
        b._fused_decode = StepSpy(b._fused_decode, has_logits=True)
        b._fused_prefill = StepSpy(b._fused_prefill, has_logits=False)
        spies[iid] = b
    trace = generate_trace(trace_spec(args.requests, args.seed))
    plens = [len(tr.prompt) for tr in trace]
    log(f"  trace: {len(trace)} requests, prompt chars {min(plens)}-"
        f"{max(plens)}, {sum(tr.prefix_id >= 0 for tr in trace)} on a "
        f"shared head")
    rids, wall = serve_trace(orch, trace)
    tokens = check_served(orch, trace, rids)
    served = {r.island_id for r in orch.results.values()}
    shared = sum(b.stats.get("prefix_tokens_skipped", 0)
                 for b in orch.batchers.values())
    check(shared > 0, f"prefix pages shared ({shared} prompt tokens "
          f"skipped)")
    for iid, b in spies.items():
        if b._fused_decode.calls:
            check(bool(b._fused_decode.finite),
                  f"{iid}: logits of all {b._fused_decode.calls} fused "
                  f"decode dispatches finite")
    log(f"  requests answered {len(rids)}, tokens generated {tokens}, "
        f"islands {sorted(served)}, ticks {orch.tick_stats['ticks']}, "
        f"wall seconds {wall:.3f}")

    log("phase lowering: served steps call the Pallas kernels")
    b = next(b for b in spies.values()
             if b._fused_decode.calls and b._fused_prefill.calls)
    for name, spy in (("fused decode", b._fused_decode),
                      ("fused prefill", b._fused_prefill)):
        check("tpu_custom_call" in spy.lowered_text(),
              f"{name} step lowers to tpu_custom_call")

    log("phase kernels: Pallas vs ref.py at the served shapes")
    check_kernels(b, np.random.default_rng(args.seed))
    return orch


def check_kernels(b, rng):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    mixer = b.pool.pages["blocks"]["slot0"]["mixer"]
    kp, vp = mixer["k_pages"], mixer["v_pages"]
    L, P, ps, Hkv, D = kp.shape
    H = b.cfg.num_heads
    used = max(2, b.pool.stats["peak_in_use"] + 1)   # pages ever written
    W = b.pages_per_seq
    scale = D ** -0.5

    def put(x):
        return jax.device_put(x, b.device)

    def compare(name, out, want):
        out = np.asarray(out, np.float32)
        want = np.asarray(want, np.float32)
        err = np.abs(out - want)
        worst = float(np.max(err / (KERNEL_TOL + KERNEL_TOL * np.abs(want))))
        check(bool(np.all(np.isfinite(out))) and worst <= 1.0,
              f"{name}: max |err| {err.max():.3e}, worst |err| / "
              f"({KERNEL_TOL:.3e}(1 + |ref|)) = {worst:.3f} <= 1")

    S = b.num_slots
    for layer in (0, L - 1):
        q = put(jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16))
        bt = put(jnp.asarray(rng.integers(1, used, (S, W)), jnp.int32))
        valid = put(jnp.asarray(rng.integers(1, W * ps + 1, (S,)), jnp.int32))
        ly = jnp.int32(layer)
        got = ops.paged_decode_attention(q, kp, vp, bt, valid, scale,
                                         layer=ly)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_decode_attention(q, kp, vp, bt, valid, scale,
                                              layer=ly)
        compare(f"paged decode B={S} N={W} layer {layer}", got, want)

        R, T = 4, b._chunk_pages_canon * ps
        q = put(jnp.asarray(rng.standard_normal((R, T, H, D)), jnp.bfloat16))
        start = put(jnp.asarray(rng.integers(0, W - T // ps + 1, (R,)) * ps,
                                jnp.int32))
        got = ops.chunked_prefill_attention(q, kp, vp, bt[:1].repeat(R, 0),
                                            start, scale, layer=ly)
        with jax.default_matmul_precision("highest"):
            want = ref.chunked_prefill_attention(q, kp, vp,
                                                 bt[:1].repeat(R, 0), start,
                                                 scale, layer=ly)
        compare(f"chunked prefill R={R} T={T} N={W} layer {layer}", got,
                want)


def phase_replicas(args, devices):
    import gc

    import jax
    import numpy as np
    from repro.configs.base import get_config
    from repro.core.islands import IslandRegistry, personal_island
    from repro.core.lighthouse import Lighthouse
    from repro.core.mist import MIST
    from repro.core.tide import TIDE
    from repro.core.tracegen import generate_trace
    from repro.core.waves import WAVES, Policy
    from repro.data.tokenizer import ByteTokenizer
    from repro.launch import serve
    from repro.models.model import get_model

    cfg = get_config(ARCH)
    params = get_model(cfg).init(
        jax.device_put(jax.random.PRNGKey(args.seed), devices[0]))
    trace = generate_trace(trace_spec(args.requests, args.seed))

    def run(n_islands):
        reg = IslandRegistry()
        for i in range(n_islands):
            isl = personal_island(f"replica-{i}", latency_ms=120,
                                  capacity_units=4.0)
            reg.register(isl, reg.attestation_token(isl.island_id))
        tide, lh = TIDE(reg), Lighthouse(reg)
        for isl in reg.all():
            lh.heartbeat(isl.island_id)
        waves = WAVES(MIST(), tide, lh, Policy(on_infeasible="queue_local"))
        orch = serve.build_orchestrator(cfg, reg, waves, params=params,
                                        seed=args.seed)
        for i, (iid, b) in enumerate(orch.batchers.items()):
            want = devices[i % len(devices)]
            held = {d for leaf in jax.tree.leaves((b.params, b.pool.pages,
                                                   b._dev_gen))
                    for d in leaf.devices()}
            check(held == {want}, f"{iid}: weights, pool and sampling "
                  f"state on {want}")
        rids, wall = serve_trace(orch, trace)
        tokens = check_served(orch, trace, rids)
        per_island = {}
        for r in rids:
            iid = orch.results[r].island_id
            per_island[iid] = per_island.get(iid, 0) + 1
        log(f"  {n_islands} island(s): requests answered {len(rids)}, "
            f"tokens generated {tokens}, per island {per_island}, "
            f"wall seconds {wall:.3f}")
        return [orch.results[r].tokens for r in rids]

    log(f"phase replicas: {ARCH}, one island per chip on "
        f"{len(devices)} chips vs one island on {devices[0]}")
    many = run(len(devices))
    gc.collect()
    one = run(1)

    model = get_model(cfg)
    tok = ByteTokenizer(cfg.vocab_size)
    eps = float(jax.numpy.finfo(jax.numpy.bfloat16).eps)
    fwd = jax.jit(lambda p, t: model.forward(p, tokens=t)[0])
    near, exact, far = 0, 0, []
    for tr, a, b in zip(trace, many, one):
        if a == b:
            exact += 1
            continue
        k = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
        ctx = tok.encode(tr.prompt) + list(a[:k])
        pad = -(-len(ctx) // 512) * 512
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(ctx)] = ctx
        lg = np.asarray(fwd(params, jax.device_put(ids, devices[0]))
                        [0, len(ctx) - 1], np.float32)
        top2 = np.sort(lg)[-2:]
        gap = float(top2[1] - top2[0])
        tol = NEAR_TIE_ULPS * eps * 2.0 ** np.floor(np.log2(abs(top2[1])))
        if gap <= tol:
            near += 1
        else:
            far.append((tr.idx, k, gap, tol))
        log(f"  request {tr.idx}: streams differ at step {k}, top-2 gap "
            f"{gap:.4f} vs near-tie tolerance {tol:.4f}")
    log(f"  streams identical {exact}, near-ties {near}, "
        f"other differences {len(far)}")
    check(not far, f"every stream difference is a near-tie {far[:4]}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve + lowering + kernel phases on one chip; "
                         "4: only the replica phase, one island per chip")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=32)
    args = ap.parse_args(argv)

    from repro.launch import serve
    cache = serve.setup_compile_cache()

    import jax
    import jax.monitoring
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"no TPU: JAX found {dev.platform} devices")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, found "
            f"{len(devices)}")
        return 1
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"compile cache {cache}")
    compile_s = [0.0, 0]

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs
            compile_s[1] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_one_chip(args, dev)
        used = [dev]
    else:
        used = devices[:args.chips]
        phase_replicas(args, used)
    log(f"compile seconds {compile_s[0]:.3f} over {compile_s[1]} "
        f"compiles; total wall seconds {time.perf_counter() - t0:.3f}")
    for d in used:
        stats = d.memory_stats() or {}
        log(f"{d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
            f"bytes_limit {stats.get('bytes_limit')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
