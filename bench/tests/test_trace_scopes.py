"""The trace reduced by the program's names, on a small hand-made
timeline: device time by named scope exclusive of nested operations, idle
gaps labelled by the innermost span open, program spans included; and the
name stacks read from the HLO a real (CPU) profiler trace stores."""
import glob
from types import SimpleNamespace as NS

import pytest

import trace_scopes as ts

MS = 1_000_000
DECODE = "jit_fused_decode_step(3)"


def ev(name, s_ms, e_ms):
    return NS(name=name, start_ns=s_ms * MS, duration_ns=(e_ms - s_ms) * MS)


def stack(*scopes):
    return "jit(fused_decode_step)/while/body/closed_call/" + "/".join(scopes)


# the name stacks the profiler stored for the decode program
STACKS = {DECODE: {
    "while.5": "jit(fused_decode_step)/while",
    "paged_decode_attention.9": stack("attn", "paged_decode_attention"),
    "bitcast_add_fusion.2": stack("attn", "page_write", "scatter"),
    "fusion.1": stack("mlp", "dot_general"),
    "fusion.2": "jit(fused_decode_step)/lm_head/dot_general",
    "argmax.1": "jit(fused_decode_step)/emit/argmax"}}


def profile():
    """A decode step whose layer loop holds attention (with a page write
    nested in it) and an MLP, then a routing kernel; the host spans of one
    orchestrator tick around them."""
    ops = [ev("%while.5 = (s32[]) while(...)", 0, 10),
           ev("%paged_decode_attention.9 = bf16[8] custom-call()", 1, 3),
           ev("%bitcast_add_fusion.2 = bf16[8] fusion()", 3, 4),
           ev("%fusion.1 = bf16[8] fusion()", 4, 7),
           ev("%fusion.2 = bf16[8] fusion()", 7, 7.5),
           ev("%argmax.1 = s32[8] reduce()", 7.5, 8),
           ev("%fusion.9 = f32[] fusion()", 12, 13)]
    mods = [ev(DECODE, 0, 10), ev("jit_route_batch_tick(1)", 12, 13)]
    host = [ev("bench.orch_tick", 0, 20), ev("orch.tick", 0, 19),
            ev("orch.route", 0, 14), ev("orch.route.kernel", 11, 13.5),
            ev("batcher.tick", 15, 18), ev("batcher.plan", 15, 16.5),
            ev("obs.clock_anchor", 0.5, 0.5), ev("PjRtExecute", 0, 1)]
    line = lambda name, evs: NS(name=name, events=evs)  # noqa: E731
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[line("XLA Modules", mods),
                                        line("XLA Ops", ops)]),
        NS(name="/host:CPU", lines=[line("python", host)])])


def test_events_read_ops_spans_and_anchor():
    dev, ops, host, anchor = ts.events(profile())
    mods, own = ops["/device:TPU:0"]
    assert len(own) == len(dev["XLA Ops"]) == 7 and len(mods) == 2
    assert [n for n, _s, _e in host] == [
        "bench.orch_tick", "orch.tick", "orch.route", "orch.route.kernel",
        "batcher.tick", "batcher.plan"]
    assert anchor == 0.5 * MS


def test_device_scopes_are_exclusive():
    _dev, ops, _host, _a = ts.events(profile())
    got = dict(ts.device_scopes(ops, STACKS))
    assert got == pytest.approx({
        "fused_decode_step/-": 0.003,          # the loop's own 10 - 7 ms
        "fused_decode_step/attn": 0.002,
        "fused_decode_step/page_write": 0.001,
        "fused_decode_step/mlp": 0.003,
        "fused_decode_step/lm_head": 0.0005,
        "fused_decode_step/emit": 0.0005,
        "route_batch_tick/-": 0.001})
    # they add up to the device's busy time
    dev, ops, host, _a = ts.events(profile())
    r = ts.reduce(dev, ops, host, STACKS)
    assert sum(got.values()) == pytest.approx(r["busy_s"])
    # without the stored HLO every operation is unscoped
    assert {k.split("/")[1] for k, _t in ts.device_scopes(ops, {})} == {"-"}


def test_idle_gaps_take_the_innermost_span():
    dev, ops, host, _a = ts.events(profile())
    r = ts.reduce(dev, ops, host, STACKS, window=(0, 20 * MS))
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 10-12 ms: mid 11 ms, the routing kernel's host span has begun;
    # 13-20 ms: mid 16.5 ms, where batcher.plan ends inside batcher.tick
    assert gaps == pytest.approx({"orch.route.kernel": 0.002,
                                  "batcher.tick": 0.007})
    assert r["breakdown"]["device_scopes"]


def test_scope_of_takes_the_innermost():
    assert ts.scope_of(stack("attn", "page_write", "add")) == "page_write"
    assert ts.scope_of(stack("mlp", "jit(silu)")) == "mlp"
    assert ts.scope_of("jit(f)/while/body/add") is None
    assert ts.scope_of(None) is None


def test_name_stacks_from_a_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("attn"):
            y = jnp.sin(x) @ x
        with jax.named_scope("mlp"):
            return jnp.tanh(y) * 2

    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        step(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    stacks = ts.name_stacks(path)
    prog = [k for k in stacks if k.startswith("jit_step(")]
    assert len(prog) == 1
    scopes = {ts.scope_of(v) for v in stacks[prog[0]].values()}
    assert {"attn", "mlp"} <= scopes
