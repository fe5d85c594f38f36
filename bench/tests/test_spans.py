"""A ``--trace 1`` run of the harness on the CPU at a small size through
``spans.py``: the program's journal gives the window's five numbers, every
request's stages are non-negative and sum to the latency the benchmark
measured, the program's own host time per orchestrator tick agrees with
the benchmark's outside timing of it, and an untraced run's result is
the same with the tracer attached."""
import json
from pathlib import Path

import pytest

import run
import small
import spans

CELL = "qwen3-4b.chat-shared"
LIMIT = {"logit_gap": 0.03}          # as in test_faults.py


def _kw():
    cfg = json.loads((Path(run.BENCH) / "configs" / "qwen3-4b.json")
                     .read_text())
    return dict(allow_cpu=True,
                config_override=small.config(cfg["deployment"]),
                traffic_override=small.traffic(), limits_override=LIMIT)


def test_traced_run_reads_the_journal(capsys):
    argv = ["--workload", CELL, "--seed", "4294967311", "--seconds", "3"]
    assert spans.main(argv, **_kw()) == 0
    out = capsys.readouterr()
    lines = out.out.strip().splitlines()
    result, rep = json.loads(lines[-2]), json.loads(lines[-1])
    assert result["correct"] is True
    assert set(rep["journal"]) == {
        "req_orch_wait_ms", "req_slot_wait_ms", "req_prefill_ms",
        "req_decode_ms", "route_host_ms_per_pass"}
    assert all(v >= 0 for v in rep["journal"].values())
    assert rep["stage_sum_gap_ms"] < 1e-3
    prog = rep["orch_host_ms_per_tick"]
    assert prog["program"] == pytest.approx(prog["benchmark"], rel=0.25)
    assert "spans in the window" in out.err
    assert rep["negative_stages"] == 0
    assert len(rep["slowest"]) == spans.SLOWEST
    for row in rep["slowest"]:
        assert sum(row["stages_ms"].values()) == \
            pytest.approx(row["latency_ms"], abs=1e-3)


def test_attached_tracer_changes_no_untraced_result(capsys):
    from repro.obs import Tracer
    argv = ["--workload", CELL, "--seed", "7", "--seconds", "2",
            "--trace", "0"]
    assert run.main(argv, **_kw()) == 0
    plain = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    tracer = Tracer()
    with spans.attached(tracer):
        assert run.main(argv, **_kw()) == 0
    traced = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert traced["checks"] == plain["checks"]
    assert traced["attempted"] == plain["attempted"]
    assert any(e.kind == "span" for e in tracer.events)
