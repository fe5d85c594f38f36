"""The reduction from trace events to busy time, program and kernel time,
and the breakdown, on a small hand-made timeline."""
import trace_reduce as tr

MS = 1_000_000


def timeline():
    dev = {
        "XLA Modules": [("jit_fused_prefill_step(7)", 0, 4 * MS),
                        ("jit_fused_decode_step(9)", 5 * MS, 7 * MS),
                        ("jit_fused_decode_step(9)", 10 * MS, 12 * MS)],
        "XLA Ops": [("chunked_prefill_attention", 1 * MS, 2 * MS),
                    ("fusion.3", 2 * MS, 4 * MS),
                    ("paged_decode_attention", 5 * MS, 6 * MS),
                    ("paged_decode_attention", 10 * MS, 11 * MS)],
    }
    host = [("bench.orch_tick", 0, 9 * MS),
            ("bench.batcher_tick", 0, 4 * MS),
            ("bench.orch_tick", 9500000, 12 * MS)]
    return dev, host


def test_busy_modules_kernels():
    r = tr.reduce(*timeline(), window=(0, 14 * MS))
    assert abs(r["busy_s"] - 0.008) < 1e-12
    assert abs(r["window_s"] - 0.014) < 1e-12
    assert r["modules"]["fused_decode_step"] == [0.002, 0.002]
    assert r["decode_steps"] == 2
    assert abs(r["kernels"]["paged_decode_attention"] - 0.002) < 1e-12
    assert abs(r["kernels"]["chunked_prefill_attention"] - 0.001) < 1e-12


def test_idle_gaps_labelled_by_open_host_span():
    r = tr.reduce(*timeline(), window=(0, 14 * MS))
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 4-5 ms inside an orchestrator tick, outside the batcher, and 7-10 ms
    # (its middle, 8.5 ms, still inside the first orchestrator tick)
    assert abs(gaps["bench.orch_tick"] - 0.004) < 1e-12
    # 12-14 ms: nothing open
    assert abs(gaps["no benchmark span (waiting)"] - 0.002) < 1e-12
    ops = dict(r["breakdown"]["device_ops"])
    assert abs(ops["fusion.3"] - 0.002) < 1e-12


def test_window_clips_events():
    r = tr.reduce(*timeline(), window=(6 * MS, 11 * MS))
    assert abs(r["busy_s"] - 0.002) < 1e-12
    assert r["modules"]["fused_decode_step"] == [0.001, 0.001]


def test_module_names():
    assert tr.module_name("jit_fused_decode_step(123)") == "fused_decode_step"
    assert tr.module_name("fused_prefill_step") == "fused_prefill_step"
