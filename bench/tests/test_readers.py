"""Every per-layer reader on a hand-made context: each reads a number in
range, or nothing when there is nothing to read."""
import json
from pathlib import Path

import pytest

import run
import tracing

BENCH = Path(run.BENCH)


def context(**over):
    cfg = json.loads((BENCH / "configs" / "qwen3-4b.json").read_text())
    ctx = dict(
        cfg=cfg, kind="TPU v5 lite", slots=16,
        trace={"busy_s": 5.9, "window_s": 6.0,
               "modules": {"fused_prefill_step": [0.02] * 190,
                           "fused_decode_step": [0.026] * 90},
               "kernels": {"chunked_prefill_attention": 1.2,
                           "paged_decode_attention": 1.1},
               "decode_steps": 90,
               "breakdown": {"device_ops": [], "idle_gaps": []}},
        window={"decode_tokens": 900, "decode_steps": 90,
                "prefix_skipped": 6000, "prompt_tokens_sent": 30000},
        orch_host_ms=[4.0, 6.0],
        decode_traced={"ctx_sum": 1200 * 1400, "tokens": 1200},
        prefill_traced={"tokens": 12000, "ctx_sum": 12000 * 1200,
                        "attn_flops": 8e12, "attn_bytes": 2e9,
                        "heads": 8, "complete": True})
    ctx.update(over)
    return tracing.Context(**ctx)


NAMES = sorted(p.stem for p in (BENCH / "metrics").glob("*.py"))


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_in_range(name):
    v = run.load_reader(name).read(context())
    assert v is not None and v > 0
    if name.endswith(("_roofline", "mfu", "_share", "occupancy")):
        assert v <= 100.0


@pytest.mark.parametrize("name", NAMES)
def test_reader_with_nothing_to_read(name):
    empty = context(trace={"busy_s": 0.0, "window_s": 0.0, "modules": {},
                           "kernels": {}, "decode_steps": 0},
                    window={}, orch_host_ms=[],
                    decode_traced={"ctx_sum": 0, "tokens": 0},
                    prefill_traced={"tokens": 0, "ctx_sum": 0,
                                    "attn_flops": 0, "attn_bytes": 0,
                                    "heads": 0, "complete": False})
    assert run.load_reader(name).read(empty) is None
