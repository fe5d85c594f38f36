"""Fused-tick dispatch path: bit-exact parity with the unfused batcher,
dispatch-count regression bounds, and device-resident stream handling at
the preemption/sampling boundaries.

The fused path collapses every chunk run of a tick into ONE batched
prefill dispatch and keeps greedy sampling state device-resident, so a
tick issues at most two model programs; these tests pin the contract
that fusion is a pure wall-clock optimization — same tokens, same work
clock, same sharing telemetry, fewer launches.
"""
import pytest

from repro.configs.base import get_config
from repro.serving.batcher import PagedContinuousBatcher


@pytest.fixture(scope="module")
def cfg():
    return get_config("smollm-135m").reduced()


@pytest.fixture(scope="module")
def params(cfg):
    import jax

    from repro.models.model import get_model
    return get_model(cfg).init(jax.random.PRNGKey(0), "float32")


PREFIX = "shared clinical preamble for the cohort under review. "
MIXED = [
    (PREFIX + "alpha " * 12, 8, 0),
    (PREFIX + "beta " * 16, 6, 0),
    ("an unrelated billing request with no shared head", 8, 1),
    (PREFIX + "gamma " * 4, 10, 0),
    ("tiny", 5, None),
    (PREFIX + "delta " * 20, 7, 0),
]


def _run(cfg, params, workload, fused, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_len", 96)
    kw.setdefault("page_size", 16)
    b = PagedContinuousBatcher(cfg, params=params, fused=fused, **kw)
    rids = [b.submit(p, max_new_tokens=mn, trust_tier=t)
            for p, mn, t in workload]
    done = b.run_until_done()
    return b, [done[r] for r in rids]


def test_fused_bitexact_and_workclock_mixed(cfg, params):
    """Greedy token streams, the virtual work clock and every logical
    scheduling stat must be identical fused vs unfused on the mixed
    (long/short, tiered/untiered, shared/private) workload — only the
    device-launch counters may differ."""
    bu, outu = _run(cfg, params, MIXED, fused=False)
    bf, outf = _run(cfg, params, MIXED, fused=True)
    assert outf == outu
    assert bf.work_clock == bu.work_clock
    for key in ("admissions", "prefill_dispatches", "decode_steps",
                "decode_tokens", "share_hits", "prefix_tokens_skipped",
                "prefill_chunk_tokens", "preemptions"):
        assert bf.stats[key] == bu.stats[key], key
    assert bf.stats["device_dispatches"] < bu.stats["device_dispatches"]


def test_fused_bitexact_shared_prefix(cfg, params):
    """Same-tier prefix sharing (admission attach AND late dispatch-time
    attach) must survive fusion bit-exactly — same-dispatch cross-row
    attaches read the writer row's K/V."""
    wl = [(PREFIX + f"variant {i} " * 3, 6, 2) for i in range(6)]
    bu, outu = _run(cfg, params, wl, fused=False)
    bf, outf = _run(cfg, params, wl, fused=True)
    assert outf == outu
    assert bf.stats["share_hits"] == bu.stats["share_hits"] > 0
    assert bf.stats["prefix_tokens_skipped"] == \
        bu.stats["prefix_tokens_skipped"] > 0


def test_fused_tick_dispatch_count_bound(cfg, params):
    """The regression gate: a fused tick issues at most 3 model programs
    (1 batched prefill + 1 decode in practice) however many chunk runs
    the budget admits, while the unfused path launches one per run."""
    wl = [(f"request number {i} " + "filler " * (4 + 3 * i), 5, i % 3)
          for i in range(8)]
    bu, _ = _run(cfg, params, wl, fused=False, num_slots=4,
                 prefill_token_budget=96)
    bf, _ = _run(cfg, params, wl, fused=True, num_slots=4,
                 prefill_token_budget=96)
    assert bf.stats["tick_dispatches_max"] <= 3
    assert bf.stats["tick_dispatches_max"] < \
        bu.stats["tick_dispatches_max"]


def test_fused_preemption_parity(cfg, params):
    """Pool-exhaustion preemption must materialize the victim's
    device-resident tail into its resume ticket: streams stay identical
    on a pool small enough to force evictions."""
    wl = [(f"tiny seed {i}", 40, i % 2) for i in range(4)]
    bu, outu = _run(cfg, params, wl, fused=False, num_pages=6)
    bf, outf = _run(cfg, params, wl, fused=True, num_pages=6)
    assert bf.stats["preemptions"] == bu.stats["preemptions"] > 0
    assert outf == outu


def test_constant_shape_bitexact_with_fixed_geometry(cfg, params):
    """Constant-shape dispatch (the access-pattern-leakage mitigation)
    pads every launch to one fixed prefill and one fixed decode geometry;
    it must stay a pure shape change: identical greedy streams on the
    mixed workload, and a deterministic work clock that counts only real
    tokens (so padding costs launches nothing on the gated proxy)."""
    bf, outf = _run(cfg, params, MIXED, fused=True)
    bc, outc = _run(cfg, params, MIXED, fused=True, constant_shape=True)
    assert outc == outf
    pre = {s[1:] for s in bc.dispatch_shapes if s[0] == "prefill"}
    dec = {s[1:] for s in bc.dispatch_shapes if s[0] == "decode"}
    assert len(pre) <= 1 and len(dec) <= 1, (pre, dec)
    if bc.stats["preemptions"] == bf.stats["preemptions"]:
        assert bc.work_clock == bf.work_clock
    else:                       # scheduling drift may shift recompute
        assert bc.work_clock <= 1.25 * bf.work_clock


def test_constant_shape_requires_fused_chunked_path(cfg):
    with pytest.raises(ValueError, match="constant_shape"):
        PagedContinuousBatcher(cfg, num_slots=2, max_len=64,
                               fused=False, constant_shape=True)
    with pytest.raises(ValueError, match="constant_shape"):
        PagedContinuousBatcher(cfg, num_slots=2, max_len=64,
                               prefill="full", constant_shape=True)


def test_fused_stochastic_parity(cfg, params):
    """temperature > 0 falls back to host-side per-slot-key sampling but
    keeps the fused dispatches; the sampled streams must match the
    unfused path draw for draw."""
    wl = [(p, mn, t) for p, mn, t in MIXED[:4]]
    bu, outu = _run(cfg, params, wl, fused=False, temperature=0.9)
    bf, outf = _run(cfg, params, wl, fused=True, temperature=0.9)
    assert outf == outu


@pytest.mark.parametrize("fused", [True, False])
def test_decode_kv_page_counters_match_hand_count(cfg, params, fused):
    """``decode_kv_pages_live`` counts the pages each decoding sequence
    holds in every decode dispatch, ``decode_kv_pages_grid`` the
    dispatch's table capacity (slots x width). One request of ``n``
    answer tokens after an ``L``-token prompt decodes ``n - 1`` steps,
    feeding position ``L + k`` at step ``k``: it holds ``(L + k) // 16
    + 1`` pages there, and the table is that many pages wide (the fused
    path rounds the width up to a power of two, at most the 96 // 16
    pages of a slot)."""
    n = 24
    b, _ = _run(cfg, params, [("a short prompt", n, 0)], fused=fused)
    L = b.request_log[0]["prompt_tokens"]
    held = [(L + k) // 16 + 1 for k in range(n - 1)]
    width = [min(1 << (p - 1).bit_length(), 6) if fused else p
             for p in held]
    assert b.stats["decode_steps"] == n - 1
    assert b.stats["decode_kv_pages_live"] == sum(held)
    assert b.stats["decode_kv_pages_grid"] == 3 * sum(width)
    assert len(set(held)) > 1          # the answer crosses a page boundary
