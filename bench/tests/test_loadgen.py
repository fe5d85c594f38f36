"""The load generator: every seed gives the same work, the warm-up is the
same for every seed, the lengths fit the deployment's context, and a mix
of documents and questions comes through MIST's sanitization with its
documents unchanged and the PII of its questions gone."""
import json
from pathlib import Path

import loadgen

CONFIGS = Path(__file__).resolve().parents[1] / "configs"

# a document Q&A mix, as a traffic file would give it: the generator's
# question path, which a RAG cell's file drives
DOCS = {"arrival": {"kind": "poisson", "rate_per_s": 0.8},
        "shape_seed": 1202,
        "question": {"min": 32, "max": 256, "alpha": 1.1},
        "output": {"min": 16, "max": 128, "alpha": 1.4},
        "shared": {"corpus": 16, "min": 2048, "max": 6144, "alpha": 1.1,
                   "zipf_s": 1.1, "reuse_p": 1.0},
        "tiers": [[1, 0.5], [2, 0.5]],
        "tenants": {"n": 64, "zipf_s": 1.1},
        "priority": "secondary",
        "warmup": {"requests": 8, "seed": 7, "max_new": 16,
                   "route_pools": [1, 2, 4]}}


def test_every_seed_gives_the_same_work():
    t = loadgen.load("chat-shared")
    a, _ = loadgen.build(t, 1, 30)
    b, _ = loadgen.build(t, 2 ** 40 + 3, 30)
    assert len(a) == len(b) == round(t["arrival"]["rate_per_s"] * 30)
    key = [(r.max_new, r.tier, r.shared_id, len(r.prompt), r.due) for r in a]
    assert key == [(r.max_new, r.tier, r.shared_id, len(r.prompt), r.due)
                   for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert 0 < a[0].due and max(r.due for r in a) < 30


def test_warmup_is_the_same_for_every_seed():
    for t in (loadgen.load("chat-shared"), DOCS):
        a, _ = loadgen.build(t, 1, 0, phase="warmup")
        b, _ = loadgen.build(t, 99, 0, phase="warmup")
        assert [r.prompt for r in a] == [r.prompt for r in b]


def test_lengths_fit_the_context():
    dep = json.loads((CONFIGS / "qwen3-4b.json").read_text())["deployment"]
    for t, max_len in ((loadgen.load("chat-shared"), dep["max_len"]),
                       (DOCS, 8192)):
        reqs, _ = loadgen.build(t, 5, 40)
        reach = loadgen.reach_request(t, 5, t["warmup"]["max_new"])
        for r in reqs + [reach]:
            # begin token + prompt + answer must fit the slot's context
            assert 1 + len(r.prompt) + r.max_new <= max_len


def test_documents_survive_sanitization_and_pii_does_not():
    from repro.core.mist import MIST
    reqs, corpus = loadgen.build(DOCS, 17, 20)
    mist = MIST()
    for doc in corpus:
        assert mist.stage1(doc)[2] == []
    for r in reqs[:12]:
        out, _store = mist.sanitize([r.prompt], seed=3)
        assert out[0].startswith(corpus[r.shared_id])
        assert r.pii and not any(v in out[0] for v in r.pii)
