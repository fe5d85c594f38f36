"""The plain reference: a float32 Qwen3 forward pass in straightforward
``jax.numpy``, and its control, the same pass in float8.

It follows the published description (RMSNorm with eps 1e-6, q- and
k-norm over each head, rotary embedding with theta from the
configuration, grouped-query attention, SwiGLU, the configuration's tied
or untied output head) and imports nothing of the program. Its weights
are the benchmark's own draw (``weights.layer_weights``), upcast to
float32 one layer at a time, and every product runs at
``Precision.HIGHEST`` so that the TPU does not round its operands to
bfloat16.

The pass runs over whole sequences (prompt plus served tokens) padded to
one length, layer by layer, with rows and queries in blocks so that it
fits beside nothing else on the chip. It returns, for each position that
produced a served token, the reference's best logit and the logits of the
tokens of interest.

The control (``fp8=True``) computes the same pass with every matrix
product's operands stored in float8 (e4m3, scaled per row of activations
and per output column of weights, accumulated in float32): the next
precision below the bfloat16 the configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST
ROWS = 512        # rows per block of the projections and the MLP
QBLOCK = 256      # queries per block of attention
HEAD_ROWS = 256   # positions per block of the output head
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis):
    """Round ``x`` to float8 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.maximum(s, 1e-30)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, fp8):
    """x (..., k) @ w (k, n) in float32, operands rounded to float8 in
    the control."""
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """Rotary embedding, the published rotate-half form. x (..., T, H, D),
    pos (T,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    f = pos.astype(jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([f, f], -1)[:, None, :]          # (T, 1, D)
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _rowwise(fn, x, *args):
    """Apply ``fn(rows, *args)`` to the rows of x (N, d) in blocks."""
    n, d = x.shape
    y = jax.lax.map(lambda r: fn(r, *args), x.reshape(n // ROWS, ROWS, d))
    return y.reshape(n, -1)


def _attend(q, k, v):
    """Causal grouped-query attention of one sequence: q (T, H, D),
    k/v (T, Hkv, D); queries in blocks."""
    T, H, D = q.shape
    hkv = k.shape[1]
    g = H // hkv
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK, 0)
        qb = qb.reshape(QBLOCK, hkv, g, D)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HI) * D ** -0.5
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)
        return o.reshape(QBLOCK, H, D)

    o = jax.lax.map(block, jnp.arange(T // QBLOCK))
    return o.reshape(T, H, D)


@partial(jax.jit, static_argnames=("hp", "fp8"))
def _layer(x, lw, hp, fp8):
    """One decoder layer over a batch x (B, T, d) of padded sequences."""
    eps, theta, h, hkv, hd = hp
    B, T, d = x.shape
    pos = jnp.arange(T)

    def qkv(r):
        n = rms_norm(r, lw["input_layernorm"], eps)
        return jnp.concatenate([_mm(n, lw["q_proj"], fp8),
                                _mm(n, lw["k_proj"], fp8),
                                _mm(n, lw["v_proj"], fp8)], -1)

    y = _rowwise(qkv, x.reshape(B * T, d)).reshape(B, T, -1)
    q = y[..., :h * hd].reshape(B, T, h, hd)
    k = y[..., h * hd:(h + hkv) * hd].reshape(B, T, hkv, hd)
    v = y[..., (h + hkv) * hd:].reshape(B, T, hkv, hd)
    q = rope(rms_norm(q, lw["q_norm"], eps), pos, theta)
    k = rope(rms_norm(k, lw["k_norm"], eps), pos, theta)
    if fp8:  # cached K/V in float8 too, one scale per token and head
        k, v = _q8(k, -1), _q8(v, -1)
    o = jax.lax.map(lambda a: _attend(*a), (q, k, v))
    x = x + _rowwise(lambda r: _mm(r, lw["o_proj"], fp8),
                     o.reshape(B * T, h * hd)).reshape(B, T, d)

    def mlp(r):
        n = rms_norm(r, lw["post_attention_layernorm"], eps)
        a = _mm(n, lw["gate_proj"], fp8)
        return _mm(jax.nn.silu(a) * _mm(n, lw["up_proj"], fp8),
                   lw["down_proj"], fp8)

    return x + _rowwise(mlp, x.reshape(B * T, d)).reshape(B, T, d)


@partial(jax.jit, static_argnums=(2,))
def _layer_weights_f32(key, layer, wt):
    return {k: v.astype(jnp.float32)
            for k, v in W.layer_weights(key, layer, dict(wt)).items()}


@partial(jax.jit, static_argnums=(1,))
def _global_f32(key, wt):
    return {k: v.astype(jnp.float32)
            for k, v in W.global_weights(key, dict(wt)).items()}


@partial(jax.jit, static_argnames=("eps", "fp8", "tied"))
def _head(h, norm, emb_or_head, tokens, eps, fp8, tied):
    """Logits of rows h (M, d); returns (best logit, logit of ``tokens``
    (M, k) columns, argmax)."""
    n = rms_norm(h, norm, eps)
    w = emb_or_head.T if tied else emb_or_head
    if fp8:
        n, w = _q8(n, -1), _q8(w, 0)
    lg = jnp.einsum("md,dv->mv", n, w, precision=HI)
    picked = jnp.take_along_axis(lg, tokens, 1)
    return lg.max(-1), picked, jnp.argmax(lg, -1).astype(jnp.int32)


def run(cfg: dict, seed: int, seqs, positions, tokens, fp8: bool = False,
        batch: int = 4, pad_to: int = 0):
    """Reference (or control) logits for ``seqs`` (lists of token ids).

    ``positions[i]`` lists the positions of ``seqs[i]`` whose next-token
    logits are wanted and ``tokens[i]`` (same length, each a tuple of k
    ids) the ids whose logits to return there. Returns, per sequence, a
    list of (best logit, (logits of the k ids), argmax id) per position.
    Sequences run ``batch`` at a time, layer by layer, padded to
    ``pad_to`` positions (at least the longest), so that every run of a
    cell compiles the same programs."""
    from counts import widths
    w = widths(cfg)
    wt = tuple(sorted(w.items()))
    hp = (float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]), w["h"],
          w["hkv"], w["hd"])
    key = W.base_key(seed)
    g = _global_f32(key, wt)
    T = max(max(len(s) for s in seqs), pad_to)
    T = -(-T // ROWS) * ROWS
    out = [None] * len(seqs)
    with jax.default_matmul_precision("highest"):
        for b0 in range(0, len(seqs), batch):
            idx = list(range(b0, min(b0 + batch, len(seqs))))
            ids = np.zeros((batch, T), np.int32)
            for r, i in enumerate(idx):
                ids[r, :len(seqs[i])] = seqs[i]
            x = g["embed_tokens"][jnp.asarray(ids)]
            if fp8:
                x = _q8(x, -1)
            for l in range(w["layers"]):
                lw = _layer_weights_f32(key, jnp.int32(l), wt)
                x = _layer(x, lw, hp, fp8)
                del lw
            for r, i in enumerate(idx):
                m = len(positions[i])
                mp = -(-m // HEAD_ROWS) * HEAD_ROWS   # one compile per size
                pos = np.zeros((mp,), np.int32)
                pos[:m] = positions[i]
                tk = np.asarray(tokens[i], np.int32).reshape(m, -1)
                toks = np.zeros((mp, tk.shape[1]), np.int32)
                toks[:m] = tk
                best, picked, am = _head(
                    x[r][jnp.asarray(pos)], g["norm"],
                    g["embed_tokens"] if w["tied"] else g["lm_head"],
                    jnp.asarray(toks), hp[0], fp8, w["tied"])
                out[i] = (jax.device_get(best)[:m],
                          jax.device_get(picked)[:m],
                          jax.device_get(am)[:m])
            del x
    return out
