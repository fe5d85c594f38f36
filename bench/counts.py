"""Operations, bytes and parameters of a Qwen3 decoder, computed from the
published widths in a configuration file, and the peaks of each chip.

Everything a roofline or utilisation share divides by lives here, so every
implementation of a kernel is held to the same count: the count follows
the work (tokens and the context each attends over), never the shapes a
kernel happens to read.
"""
from __future__ import annotations

BF16 = 2  # bytes per stored weight, activation and cached K/V element

# Published peaks per chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s per chip).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The chip's peaks; an unknown chip is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def widths(cfg: dict) -> dict:
    """The sizes the counts need, from a configuration file's keys."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    return dict(d=d, ff=cfg["intermediate_size"],
                h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
                hd=hd, layers=cfg["num_hidden_layers"],
                vocab=cfg["vocab_size"],
                tied=bool(cfg["tie_word_embeddings"]))


def layer_params(cfg: dict) -> int:
    w = widths(cfg)
    attn = w["d"] * (w["h"] + 2 * w["hkv"]) * w["hd"] + w["h"] * w["hd"] * w["d"]
    mlp = 3 * w["d"] * w["ff"]
    norms = 2 * w["d"] + 2 * w["hd"]
    return attn + mlp + norms


def param_count(cfg: dict) -> int:
    """Every weight the model stores: layers, embedding, output head (when
    untied) and the final norm."""
    w = widths(cfg)
    embed = w["vocab"] * w["d"]
    head = 0 if w["tied"] else w["vocab"] * w["d"]
    return w["layers"] * layer_params(cfg) + embed + head + w["d"]


def weight_bytes(cfg: dict) -> int:
    return param_count(cfg) * BF16


def kv_bytes_per_token(cfg: dict) -> int:
    """Cached K and V of one token over every layer."""
    w = widths(cfg)
    return w["layers"] * 2 * w["hkv"] * w["hd"] * BF16


def linear_flops_per_token(cfg: dict) -> int:
    """Matrix-multiply operations of one token through every layer's
    projections and MLP (2 per multiply-add), without attention scores
    and without the output head."""
    w = widths(cfg)
    per_layer = 2 * (w["d"] * (w["h"] + 2 * w["hkv"]) * w["hd"]
                     + w["h"] * w["hd"] * w["d"] + 3 * w["d"] * w["ff"])
    return w["layers"] * per_layer


def head_flops_per_token(cfg: dict) -> int:
    w = widths(cfg)
    return 2 * w["d"] * w["vocab"]


def attn_flops(cfg: dict, ctx_sum: int) -> int:
    """Score and value products of tokens whose attended contexts sum to
    ``ctx_sum`` positions, over every layer (QK^T and PV, 2 each)."""
    w = widths(cfg)
    return w["layers"] * 4 * w["h"] * w["hd"] * ctx_sum


def causal_ctx_sum(start: int, stop: int) -> int:
    """Sum of (p + 1) for positions p in [start, stop): the context each
    token of a causal run attends over."""
    if stop <= start:
        return 0
    return (stop * (stop + 1) - start * (start + 1)) // 2


def model_flops(cfg: dict, tokens: int, ctx_sum: int,
                head_tokens: int) -> int:
    """What the model needs for ``tokens`` tokens through every layer,
    whose attended contexts sum to ``ctx_sum``, with logits computed for
    ``head_tokens`` of them (the tokens that yield an output token)."""
    return (tokens * linear_flops_per_token(cfg) + attn_flops(cfg, ctx_sum)
            + head_tokens * head_flops_per_token(cfg))


def decode_attn_work(cfg: dict, ctx_sum: int, tokens: int) -> tuple:
    """(operations, bytes) of the paged decode attention kernel for
    ``tokens`` decoded tokens whose contexts sum to ``ctx_sum``: every
    step reads its sequence's cached K and V once per layer, plus the
    query and the output."""
    w = widths(cfg)
    kv = w["layers"] * ctx_sum * 2 * w["hkv"] * w["hd"] * BF16
    qo = w["layers"] * tokens * 2 * w["h"] * w["hd"] * BF16
    return attn_flops(cfg, ctx_sum), kv + qo


def prefill_attn_work(cfg: dict, runs) -> tuple:
    """(operations, bytes) of the chunked prefill attention kernel for
    computed prompt runs ``[(start, stop), ...]`` of positions: causal
    scores and values, and the least traffic the work needs — each run
    reads the K and V of its whole context once per layer, plus its
    queries and outputs."""
    w = widths(cfg)
    fl, by = 0, 0
    for start, stop in runs:
        fl += attn_flops(cfg, causal_ctx_sum(start, stop))
        by += w["layers"] * (stop * 2 * w["hkv"] * w["hd"]
                             + (stop - start) * 2 * w["h"] * w["hd"]) * BF16
    return fl, by


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> float:
    """Least time the chip could take for the work, over the time it
    took, as a percentage."""
    pk = peaks(device_kind)
    least = max(flops / pk["flops_bf16"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds
