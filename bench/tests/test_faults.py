"""Whole runs of the harness on the CPU at a small size, past its look for
a chip, with the timed path broken underneath: ``correct`` must come out
false for each fault a serving cell can have, and true without one."""
import json
from pathlib import Path

import pytest

import faults
import run
import small

CELL = "qwen3-4b.chat-shared"


# The cell's limit is set at its full size (0.308 logits, 3.2x the widest
# gap of 13 sound seeds there; the float8 control reads 0.41 or more and a
# decode step that hands back its pool unchanged 0.50 or more). At this
# size a sound run's widest gap is ~0.005 logits, so a limit of ~6x that
# sits between it and the faults (a lost pool ~0.07, an altered token ~1).
LIMIT = {"logit_gap": 0.03}


def _run(capsys, monkeypatch, fault=None):
    cfg = json.loads((Path(run.BENCH) / "configs" / "qwen3-4b.json")
                     .read_text())
    if fault is not None:
        fault(monkeypatch.setattr)
    rc = run.main(["--workload", CELL, "--seed", "4294967311",
                   "--seconds", "3", "--trace", "0"], allow_cpu=True,
                  config_override=small.config(cfg["deployment"]),
                  traffic_override=small.traffic(), limits_override=LIMIT)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys, monkeypatch):
    res = _run(capsys, monkeypatch)
    assert res["correct"] is True
    assert list(res["checks"])[0] == "logit_gap"


@pytest.mark.parametrize("fault", list(faults.FAULTS.values()),
                         ids=list(faults.FAULTS))
def test_fault_is_caught(capsys, monkeypatch, fault):
    res = _run(capsys, monkeypatch, fault)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]
