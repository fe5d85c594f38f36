"""The counts the roofline and utilisation shares divide by."""
import json
from pathlib import Path

import pytest

import counts

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_qwen3_4b_parameters():
    c = cfg("qwen3-4b")
    assert counts.param_count(c) == 4_022_468_096        # 4.02 B
    assert round(counts.weight_bytes(c) / 1e9, 2) == 8.04
    assert counts.kv_bytes_per_token(c) == 147_456


def test_qwen3_32b_stage_parameters():
    # Qwen3-32B's published widths, cut to one stage of eight layers
    c = dict(cfg("qwen3-4b"), hidden_size=5120, intermediate_size=25600,
             num_attention_heads=64, num_key_value_heads=8,
             num_hidden_layers=8, tie_word_embeddings=False)
    assert counts.layer_params(c) == 487_598_336
    assert counts.param_count(c) == 5_456_616_448        # 5.457 B
    assert counts.kv_bytes_per_token(c) == 32_768


def test_peaks_by_device_kind():
    assert counts.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_causal_context_sums():
    assert counts.causal_ctx_sum(0, 4) == 1 + 2 + 3 + 4
    assert counts.causal_ctx_sum(2, 4) == 3 + 4
    assert counts.causal_ctx_sum(5, 5) == 0


def test_work_and_shares():
    c = cfg("qwen3-4b")
    fl, by = counts.decode_attn_work(c, ctx_sum=1000, tokens=1)
    assert fl == 36 * 4 * 32 * 128 * 1000
    assert by == 36 * (1000 * 2 * 8 * 128 + 2 * 32 * 128) * 2
    # a second at the bandwidth roofline is 100%
    assert counts.roofline_share(0, 819e9, 1.0, "TPU v5 lite") == 100.0
    fl, by = counts.prefill_attn_work(c, [(0, 64)])
    assert fl == counts.attn_flops(c, 64 * 65 // 2)
    norms = 2 * 2560 + 2 * 128
    assert counts.linear_flops_per_token(c) == \
        2 * 36 * (counts.layer_params(c) - norms)
