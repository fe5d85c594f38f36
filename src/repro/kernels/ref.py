"""Pure-jnp oracles for every Pallas kernel in this package.

Each function mirrors its kernel's signature exactly; tests sweep
shapes/dtypes and assert_allclose kernel-vs-oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def flash_attention(q, k, v, scale, causal=True):
    """q (BH, Sq, D); k/v (BHkv, Sk, D) with BH = BHkv * G. fp32 math."""
    BH, Sq, D = q.shape
    BHkv, Sk, _ = k.shape
    G = BH // BHkv
    kq = jnp.repeat(k, G, axis=0)
    vq = jnp.repeat(v, G, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   kq.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None]
        s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqk,bkd->bqd", p, vq.astype(jnp.float32))
    return o.astype(q.dtype)


def decode_attention(q, k, v, valid_len, scale):
    """q (B, H, D); k/v (B, S, Hkv, D); valid_len scalar int32."""
    B, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(jnp.arange(S)[None, None, None, :] < valid_len, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_table, valid_lens,
                           scale, layer=None):
    """q (B, H, D); k_pages/v_pages (P, page_size, Hkv, D), or
    (L, P, page_size, Hkv, D) read at ``layer``; block_table (B, N) int32;
    valid_lens (B,) int32. Gathers each sequence's K/V through its block
    table, masks positions >= valid_lens[b]."""
    if k_pages.ndim == 5:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
    B, H, D = q.shape
    P, ps, Hkv, _ = k_pages.shape
    N = block_table.shape[1]
    G = H // Hkv
    k = k_pages[block_table].reshape(B, N * ps, Hkv, D)
    v = v_pages[block_table].reshape(B, N * ps, Hkv, D)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    valid = jnp.asarray(valid_lens, jnp.int32)
    s = jnp.where(jnp.arange(N * ps)[None, None, None, :]
                  < valid[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


def chunked_prefill_attention(q, k_pages, v_pages, block_table, start_pos,
                              scale, layer=None):
    """q (B, T, H, D) — T fresh tokens, token t of sequence b at absolute
    position ``start_pos[b] + t``; k_pages/v_pages (P, page_size, Hkv, D)
    (or (L, P, page_size, Hkv, D) read at ``layer``) already holding the
    chunk's own K/V; block_table (B, N) int32; start_pos (B,) int32.
    Causal over absolute positions."""
    if k_pages.ndim == 5:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
    B, T, H, D = q.shape
    P, ps, Hkv, _ = k_pages.shape
    N = block_table.shape[1]
    G = H // Hkv
    k = k_pages[block_table].reshape(B, N * ps, Hkv, D)
    v = v_pages[block_table].reshape(B, N * ps, Hkv, D)
    qg = q.reshape(B, T, Hkv, G, D)
    s = jnp.einsum("bthgd,bkhd->bthgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    start = jnp.asarray(start_pos, jnp.int32).reshape(B)
    qpos = start[:, None] + jnp.arange(T)[None]          # (B, T)
    kpos = jnp.arange(N * ps)                            # (K,)
    mask = kpos[None, None, :] <= qpos[:, :, None]       # (B, T, K)
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bthgk,bkhd->bthgd", p, v.astype(jnp.float32))
    return o.reshape(B, T, H, D).astype(q.dtype)


def ssd_chunk(x, dt, a, B_, C_):
    """Per-chunk SSD pieces (no inter-chunk recurrence).

    x (B,nc,Q,H,P), dt (B,nc,Q,H) fp32 post-softplus, a (H,) fp32 negative,
    B_/C_ (B,nc,Q,N) fp32.
    Returns: y_intra (B,nc,Q,H,P), state (B,nc,H,P,N), decay_total (B,nc,H),
             cum (B,nc,Q,H).
    """
    Bsz, nc, Q, H, P = x.shape
    N = B_.shape[-1]
    dA = dt * a  # (B,nc,Q,H)
    cum = jnp.cumsum(dA, axis=2)
    dec = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Qt,Qs,H)
    tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    L = jnp.exp(jnp.where(tri, dec, -jnp.inf))
    sc = jnp.einsum("bcqn,bckn->bcqk", C_, B_)
    att = sc[..., None] * L * dt[:, :, None, :, :]
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", att, x.astype(jnp.float32))
    total = cum[:, :, -1, :]
    w_s = jnp.exp(total[:, :, None, :] - cum) * dt
    state = jnp.einsum("bcqh,bcqn,bcqhp->bchpn", w_s, B_,
                       x.astype(jnp.float32))
    return y_intra, state, jnp.exp(total), cum


def ssd_full(x, dt, a, B_, C_, chunk):
    """Full SSD = chunk pieces + inter-chunk scan (matches models.ssm)."""
    Bsz, S, H, P = x.shape
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    xc = x.reshape(Bsz, nc, Q, H, P)
    y_intra, state, decay, cum = ssd_chunk(
        xc, dt.reshape(Bsz, nc, Q, H), a, B_.reshape(Bsz, nc, Q, -1),
        C_.reshape(Bsz, nc, Q, -1))

    def step(h, inp):
        st, dc = inp
        h_new = dc[:, :, None, None] * h + st
        return h_new, h  # emit state BEFORE this chunk

    h0 = jnp.zeros_like(state[:, 0])
    _, h_prev = jax.lax.scan(step, h0, (jnp.moveaxis(state, 1, 0),
                                        jnp.moveaxis(decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)  # (B,nc,H,P,N)
    Cc = C_.reshape(Bsz, nc, Q, -1)
    y_inter = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cc, h_prev, jnp.exp(cum))
    return (y_intra + y_inter).reshape(Bsz, S, H, P)


def rglru_scan(a, b, h0=None):
    """h_t = a_t * h_{t-1} + b_t along axis 1; a,b (B,S,C) fp32."""
    if h0 is None:
        h0 = jnp.zeros_like(a[:, 0])

    def step(h, inp):
        ai, bi = inp
        h = ai * h + bi
        return h, h

    _, hs = jax.lax.scan(step, h0, (jnp.moveaxis(a, 1, 0),
                                    jnp.moveaxis(b, 1, 0)))
    return jnp.moveaxis(hs, 0, 1)
