"""Serving depth: continuous batcher, sampling, audit log."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.serving.batcher import ContinuousBatcher
from repro.serving.sampling import sample


# ----------------------------------------------------------------- sampler

def test_sample_greedy_matches_argmax():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 100))
    out = sample(logits, jax.random.PRNGKey(1), temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_sample_top_k_support():
    logits = jnp.zeros((2, 50)).at[:, :3].set(jnp.array([5.0, 4.0, 3.0]))
    toks = [int(t) for _ in range(20)
            for t in sample(logits, jax.random.PRNGKey(_), temperature=1.0,
                            top_k=3)]
    assert set(toks) <= {0, 1, 2}


def test_sample_top_p_prunes_tail():
    logits = jnp.log(jnp.array([[0.6, 0.3, 0.05, 0.05]]))
    toks = {int(sample(logits, jax.random.PRNGKey(i), temperature=1.0,
                       top_p=0.7)[0]) for i in range(30)}
    assert toks <= {0, 1}


# ---------------------------------------------------------------- batcher

def test_continuous_batcher_completes_all():
    cfg = get_config("smollm-135m").reduced()
    b = ContinuousBatcher(cfg, num_slots=2, max_len=64)
    rids = [b.submit(f"request number {i}", max_new_tokens=4)
            for i in range(5)]
    done = b.run_until_done()
    assert sorted(done) == sorted(rids)
    assert all(isinstance(v, str) for v in done.values())
    # queue (5 requests) > slots (2): continuous admission must have
    # recycled slots
    assert b.stats["prefill_dispatches"] == 5
    assert b.stats["queued_peak"] >= 3
    assert b.stats["decode_tokens"] >= 5 * 3


def test_batcher_slot_recycling_interleaves():
    cfg = get_config("smollm-135m").reduced()
    b = ContinuousBatcher(cfg, num_slots=1, max_len=64)
    b.submit("aaa", max_new_tokens=3)
    b.submit("bbb", max_new_tokens=3)
    b.tick()
    assert b.utilization() == 1.0
    done = b.run_until_done()
    assert len(done) == 2


# -------------------------------------------------------------- audit log

def test_audit_chain_and_compliance(stack):
    from repro.core.audit import AuditedWAVES
    from repro.core.waves import Request
    from repro.core.workload import healthcare_workload
    reg, mist, tide, lh, waves = stack
    aw = AuditedWAVES(waves)
    for req, _ in healthcare_workload(40, seed=13):
        aw.route(req)
        tide.advance(0.3)
    rep = aw.log.compliance_report()
    assert rep["entries"] == 40
    assert rep["chain_valid"]
    assert rep["privacy_violations"] == []
    assert rep["unsanitized_sensitive_cloud"] == []
    assert sum(rep["placements_by_tier"].values()) + rep["rejected"] == 40


def test_audit_detects_tampering(stack):
    from repro.core.audit import AuditedWAVES
    from repro.core.waves import Request
    reg, mist, tide, lh, waves = stack
    aw = AuditedWAVES(waves)
    for q in ("hello", "patient John Doe diagnosed", "weather"):
        aw.route(Request(query=q))
    assert aw.log.verify_chain()
    aw.log.entries[1].island_id = "evil"      # tamper
    assert not aw.log.verify_chain()
