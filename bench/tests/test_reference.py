"""The float32 reference against the program's own forward pass, at a
small size on the CPU, on the benchmark's weights: the weight layout, the
norms, the rotary embedding and the attention agree."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import reference
import weights
from counts import widths

SMALL = {"hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "num_hidden_layers": 3, "vocab_size": 320, "rms_norm_eps": 1e-6,
         "rope_theta": 1000000, "tie_word_embeddings": False}


def program_cfg(cfg):
    from repro.configs.base import get_config
    return dataclasses.replace(
        get_config("qwen3-32b"), d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_layers=cfg["num_hidden_layers"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"])


def _compare(cfg, seed):
    from repro.models.model import get_model
    w = widths(cfg)
    params = weights.program_params(seed, w)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    rng = np.random.default_rng(seed)
    seq = [1] + list(rng.integers(3, cfg["vocab_size"], 40))
    with jax.default_matmul_precision("highest"):
        lg, _, _ = get_model(program_cfg(cfg)).forward(
            p32, tokens=jnp.asarray([seq], jnp.int32))
    lg = np.asarray(lg[0], np.float32)
    pos = list(range(10, 40))
    toks = [(int(np.argmax(lg[p])), int(rng.integers(0, cfg["vocab_size"])))
            for p in pos]
    (best, picked, am), = reference.run(cfg, seed, [seq], [pos], [toks])
    np.testing.assert_allclose(best, lg[pos].max(-1), rtol=0, atol=2e-4)
    want = np.take_along_axis(lg[pos], np.asarray(toks), 1)
    np.testing.assert_allclose(picked, want, rtol=0, atol=2e-4)
    assert list(am) == [t[0] for t in toks]


def test_reference_matches_program_forward_untied():
    _compare(SMALL, seed=3)


def test_reference_matches_program_forward_tied_large_seed():
    _compare(dict(SMALL, tie_word_embeddings=True), seed=2 ** 33 + 11)


def test_layer_draw_matches_stacked_draw():
    w = widths(SMALL)
    params = weights.program_params(5, w)
    lw = weights.layer_weights(weights.base_key(5), 2, w)
    got = params["blocks"]["slot0"]["mixer"]["wk"][2]
    assert bool(jnp.all(got == lw["k_proj"]))
    gain = params["blocks"]["slot0"]["mlp"]["ln"][2].astype(jnp.float32) + 1
    assert bool(jnp.all(gain == lw["post_attention_layernorm"]
                        .astype(jnp.float32)))
