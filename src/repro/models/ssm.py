"""Mamba-2 (SSD, state-space duality) block [arXiv:2405.21060].

Chunked SSD: within a chunk the sequence mixing is a masked quadratic form
(MXU-friendly); across chunks a small recurrence over per-chunk states.
``repro.kernels.ssd`` provides the Pallas TPU kernel for the chunk
computation; this module is the portable XLA implementation and the decode
(O(1) state update) path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import PSpec, rms_norm
from repro.sharding import shard


def causal_conv(x, w):
    """Depthwise causal conv: x (B,S,C), w (W,C). out[t] = sum_i w[i]*x[t-W+1+i]."""
    W = w.shape[0]
    out = x * w[-1]
    for i in range(W - 1):
        shift = W - 1 - i
        out = out + jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :x.shape[1]] * w[i]
    return out


def ssm_table(cfg):
    d, inner, N = cfg.d_model, cfg.ssm_inner, cfg.ssm_state
    H, W = cfg.ssm_heads, cfg.conv_width
    return {
        "ln": PSpec((d,), (None,), "zeros"),
        "wz": PSpec((d, inner), (None, "ssm_heads")),
        "wx": PSpec((d, inner), (None, "ssm_heads")),
        "wB": PSpec((d, N), (None, None)),
        "wC": PSpec((d, N), (None, None)),
        "wdt": PSpec((d, H), (None, "ssm_heads")),
        "dt_bias": PSpec((H,), (None,), "dt_bias"),
        "A_log": PSpec((H,), (None,), "a_log"),
        "D": PSpec((H,), (None,), "ones"),
        "conv_x": PSpec((W, inner), (None, "ssm_heads"), scale=0.5),
        "conv_B": PSpec((W, N), (None, None), scale=0.5),
        "conv_C": PSpec((W, N), (None, None), scale=0.5),
        "gn": PSpec((inner,), (None,), "zeros"),
        "wo": PSpec((inner, d), ("ssm_heads", None)),
    }


def ssm_cache_spec(cfg, batch, max_len=None):
    inner, N, W, H, Pd = (cfg.ssm_inner, cfg.ssm_state, cfg.conv_width,
                          cfg.ssm_heads, cfg.ssm_head_dim)
    return {
        "conv": ((batch, W - 1, inner + 2 * N), ("batch", None, None)),
        "h": ((batch, H, Pd, N), ("batch", "ssm_heads", None, None)),
    }


def ssd_chunked(x, dt, a, B_, C_, chunk):
    """SSD scan. x (B,S,H,P), dt (B,S,H) fp32 (post-softplus), a (H,) fp32
    (negative), B_/C_ (B,S,N) fp32. Returns (y (B,S,H,P), h_final (B,H,P,N))."""
    Bsz, S, H, Pd = x.shape
    N = B_.shape[-1]
    Q = min(chunk, S)
    S0 = S
    if S % Q:
        # pad with dt=0 steps: decay exp(0)=1, zero input -> state unchanged
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // Q
    xc = x.reshape(Bsz, nc, Q, H, Pd)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bc = B_.reshape(Bsz, nc, Q, N)
    Cc = C_.reshape(Bsz, nc, Q, N)

    dA = dtc * a  # (B,nc,Q,H), negative log decays
    cum = jnp.cumsum(dA, axis=2)  # inclusive within-chunk cumsum

    def step(h, inputs):
        xc_i, dt_i, B_i, C_i, dA_i, cum_i = inputs  # per-chunk slices
        # intra-chunk quadratic term
        # decay(t,s) = exp(cum_t - cum_s) for s<=t (per head)
        dec = cum_i[:, :, None, :] - cum_i[:, None, :, :]  # (B,Q,Q,H)
        tri = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
        # mask BEFORE exp: exp of the (positive) upper triangle overflows
        # and poisons the backward pass with inf*0 -> nan
        L = jnp.exp(jnp.where(tri, dec, -jnp.inf))
        sc = jnp.einsum("bqn,bkn->bqk", C_i, B_i)  # (B,Q,Q)
        att = sc[..., None] * L * dt_i[:, None, :, :]  # (B,Q,Qs,H)
        y_intra = jnp.einsum("bqkh,bkhp->bqhp", att, xc_i)
        # inter-chunk: contribution of carried state
        y_inter = jnp.einsum("bqn,bhpn,bqh->bqhp", C_i, h,
                             jnp.exp(cum_i))
        # new state: h' = exp(sum dA) h + sum_s exp(total - cum_s) dt_s B_s x_s
        total = cum_i[:, -1, :]  # (B,H)
        w_s = jnp.exp(total[:, None, :] - cum_i) * dt_i  # (B,Q,H)
        h_new = (jnp.exp(total)[:, :, None, None] * h +
                 jnp.einsum("bqh,bqn,bqhp->bhpn", w_s, B_i, xc_i))
        return h_new, y_intra + y_inter

    h0 = jnp.zeros((Bsz, H, Pd, N), jnp.float32)
    xs = (jnp.moveaxis(xc.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dtc, 1, 0), jnp.moveaxis(Bc, 1, 0),
          jnp.moveaxis(Cc, 1, 0), jnp.moveaxis(dA, 1, 0),
          jnp.moveaxis(cum, 1, 0))
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S, H, Pd)[:, :S0]
    return y, h_final


def ssm_apply(cfg, p, x, positions, *, mode, cache=None):
    """Mamba-2 block. Returns (x + out, new_cache_or_None)."""
    Bsz = x.shape[0]
    inner, N = cfg.ssm_inner, cfg.ssm_state
    H, Pd, W = cfg.ssm_heads, cfg.ssm_head_dim, cfg.conv_width
    h = rms_norm(x, p["ln"])
    z = jnp.einsum("bsd,di->bsi", h, p["wz"])
    xs = jnp.einsum("bsd,di->bsi", h, p["wx"])
    Bf = jnp.einsum("bsd,dn->bsn", h, p["wB"])
    Cf = jnp.einsum("bsd,dn->bsn", h, p["wC"])
    dt_raw = jnp.einsum("bsd,dh->bsh", h, p["wdt"])
    feats = jnp.concatenate([xs, Bf, Cf], axis=-1)  # pre-conv (B,S,inner+2N)
    conv_w = jnp.concatenate([p["conv_x"], p["conv_B"], p["conv_C"]], axis=-1)

    a = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,)

    if mode == "full":
        S = x.shape[1]
        pre = feats
        if cache is not None:
            # not used in prefill-from-scratch; cache carries conv tail out
            pass
        conv = causal_conv(pre, conv_w)
        conv = jax.nn.silu(conv.astype(jnp.float32))
        xs_c, B_c, C_c = jnp.split(conv, [inner, inner + N], axis=-1)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                             p["dt_bias"].astype(jnp.float32))
        xh = xs_c.reshape(Bsz, S, H, Pd)
        xh = shard(xh, "batch", None, "ssm_heads", None)
        y, h_fin = ssd_chunked(xh, dt, a, B_c, C_c, cfg.ssm_chunk)
        y = y + p["D"].astype(jnp.float32)[None, None, :, None] * xh
        new_cache = None
        if cache is not None:
            tail = pre[:, -(W - 1):, :] if S >= W - 1 else jnp.pad(
                pre, ((0, 0), (W - 1 - S, 0), (0, 0)))
            new_cache = {"conv": tail.astype(cache["conv"].dtype),
                         "h": h_fin}
    else:  # decode: one token
        conv_state = cache["conv"]  # (B, W-1, inner+2N)
        window = jnp.concatenate([conv_state.astype(feats.dtype), feats], axis=1)
        conv = jnp.einsum("bwc,wc->bc", window, conv_w)[:, None, :]
        conv = jax.nn.silu(conv.astype(jnp.float32))
        xs_c, B_c, C_c = jnp.split(conv, [inner, inner + N], axis=-1)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                             p["dt_bias"].astype(jnp.float32))  # (B,1,H)
        xh = xs_c.reshape(Bsz, 1, H, Pd)
        hprev = cache["h"].astype(jnp.float32)  # (B,H,P,N)
        dA = jnp.exp(dt[:, 0, :] * a)  # (B,H)
        upd = jnp.einsum("bh,bn,bhp->bhpn", dt[:, 0], B_c[:, 0],
                         xh[:, 0].astype(jnp.float32))
        h_new = dA[:, :, None, None] * hprev + upd
        y = jnp.einsum("bn,bhpn->bhp", C_c[:, 0], h_new)[:, None]
        y = y + p["D"].astype(jnp.float32)[None, None, :, None] * xh
        new_cache = {"conv": window[:, 1:, :].astype(cache["conv"].dtype),
                     "h": h_new}

    y = y.reshape(Bsz, -1, inner)
    gated = y * jax.nn.silu(z.astype(jnp.float32))
    out = rms_norm(gated.astype(x.dtype), p["gn"])
    out = jnp.einsum("bsi,id->bsd", out, p["wo"])
    return x + out, new_cache
