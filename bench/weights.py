"""Random weights from ``--seed``, drawn on the device in the type they are
served in, under the published parameter names.

The same per-layer draw feeds the program (all layers stacked, in one
jitted call, laid out as the program's parameter tree) and the plain
reference (one layer at a time, upcast to float32), so the reference takes
no weight the program made: both read the benchmark's own draw.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

GAIN_SD = 0.05     # norm gains: 1 + N(0, GAIN_SD^2)
EMBED_SD = 0.02    # token embedding: N(0, EMBED_SD^2)
VOCAB_BLOCKS = 16  # the vocabulary's matrices are drawn in row blocks

# published (Hugging Face) names of one decoder layer's weights, each with
# its shape as (in, out) for projections and its kind of draw
LAYER_LEAVES = ("input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
                "q_norm", "k_norm", "post_attention_layernorm", "gate_proj",
                "up_proj", "down_proj")


def base_key(seed: int):
    """A PRNG key for any whole-number seed, wider than 32 bits too."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def layer_shapes(w: dict) -> dict:
    d, hd, h, hkv, ff = w["d"], w["hd"], w["h"], w["hkv"], w["ff"]
    return {"input_layernorm": (d,), "q_proj": (d, h * hd),
            "k_proj": (d, hkv * hd), "v_proj": (d, hkv * hd),
            "o_proj": (h * hd, d), "q_norm": (hd,), "k_norm": (hd,),
            "post_attention_layernorm": (d,), "gate_proj": (d, ff),
            "up_proj": (d, ff), "down_proj": (ff, d)}


def _draw(key, shape, kind):
    if kind == "gain":
        return 1.0 + GAIN_SD * jax.random.normal(key, shape, jnp.float32)
    if kind == "embed":
        return EMBED_SD * jax.random.normal(key, shape, jnp.float32)
    return jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5


def _kind(name):
    if name.endswith("norm") or name.endswith("layernorm"):
        return "gain"
    return "proj"


def layer_weights(key, layer, w: dict) -> dict:
    """One decoder layer's weights (bfloat16), keyed by published name.
    ``layer`` may be traced."""
    out = {}
    for i, (name, shape) in enumerate(layer_shapes(w).items()):
        k = jax.random.fold_in(jax.random.fold_in(key, 1000 + i), layer)
        out[name] = _draw(k, shape, _kind(name)).astype(jnp.bfloat16)
    return out


def _vocab_rows(key, w, kind, scale_rows):
    """A (vocab, d) matrix drawn in VOCAB_BLOCKS blocks of rows, so that
    no float32 temporary of the whole matrix is ever live."""
    v, d = w["vocab"], w["d"]
    n = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1

    def block(i):
        k = jax.random.fold_in(key, i)
        x = jax.random.normal(k, (v // n, d), jnp.float32)
        sd = EMBED_SD if kind == "embed" else scale_rows
        return (x * sd).astype(jnp.bfloat16)

    return jax.lax.map(block, jnp.arange(n)).reshape(v, d)


def global_weights(key, w: dict) -> dict:
    """Embedding, final norm and (untied) output head, in bfloat16. The
    head is drawn as its (vocab, d) transpose, row blocks like the
    embedding, with the scale of a projection from d."""
    out = {"embed_tokens": _vocab_rows(jax.random.fold_in(key, 1), w,
                                       "embed", None),
           "norm": _draw(jax.random.fold_in(key, 2), (w["d"],),
                         "gain").astype(jnp.bfloat16)}
    if not w["tied"]:
        out["lm_head"] = _vocab_rows(jax.random.fold_in(key, 3), w, "proj",
                                     w["d"] ** -0.5).T
    return out


def _gain_offset(g):
    # the program scales a norm by (1 + w); g - 1 is exact in bfloat16
    return (g.astype(jnp.float32) - 1.0).astype(jnp.bfloat16)


@partial(jax.jit, static_argnums=(1,))
def _program_params(key, wt):
    w = dict(wt)
    # one layer at a time, so only one layer's float32 draw is ever live
    lw = jax.lax.map(lambda l: layer_weights(key, l, w),
                     jnp.arange(w["layers"], dtype=jnp.int32))
    g = global_weights(key, w)
    mixer = {"ln": _gain_offset(lw["input_layernorm"]),
             "wq": lw["q_proj"], "wk": lw["k_proj"], "wv": lw["v_proj"],
             "wo": lw["o_proj"], "q_norm": _gain_offset(lw["q_norm"]),
             "k_norm": _gain_offset(lw["k_norm"])}
    mlp = {"ln": _gain_offset(lw["post_attention_layernorm"]),
           "w_gate": lw["gate_proj"], "w_up": lw["up_proj"],
           "w_down": lw["down_proj"]}
    tree = {"embed": g["embed_tokens"], "final_norm": _gain_offset(g["norm"]),
            "blocks": {"slot0": {"mixer": mixer, "mlp": mlp}}}
    if not w["tied"]:
        tree["lm_head"] = g["lm_head"]
    return tree


def program_params(seed: int, w: dict, device=None):
    """The program's parameter tree, drawn in one jitted call on
    ``device`` (default: the first device)."""
    key = base_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _program_params(key, tuple(sorted(w.items())))
