"""The control at a size a test can hold: the reference computed with
float8 operands, put in the program's place over the same prompts and
served tokens, must read far above the program (bfloat16 with float32
accumulation) on the same seed, or the logit-gap limit could not tell
them apart."""
import json
from pathlib import Path

import control
import loadgen
import run
import small


def test_control_reads_above_the_program():
    import jax
    cfg = json.loads((Path(run.BENCH) / "configs" / "qwen3-4b.json")
                     .read_text())
    cfg = dict(cfg, **small.config(cfg["deployment"]))
    traffic = dict(loadgen.load("chat-shared"), **small.traffic())
    ratios = []
    for seed in (3, 2 ** 35 + 1):
        r = control.one_seed("qwen3-4b.chat-shared", cfg, traffic, seed, 3.0,
                             jax.devices()[0], control=True)
        assert r["tokens_compared"] > 0 and not any(r["checks"].values())
        ratios.append(r["control_gap"] / max(r["logit_gap"], 1e-9))
        # through the harness's comparison, with a limit between the two
        # readings: the program is correct, the control in its place not
        limit = (r["logit_gap"] * r["control_gap"]) ** 0.5
        r = control.judged(r, {"logit_gap": limit})
        assert r["correct"] is True and r["control_correct"] is False
    assert min(ratios) >= 3.0, ratios
