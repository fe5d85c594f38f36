"""GQA attention: reference, XLA-blocked (flash-style) and decode paths.

The Pallas TPU kernels in ``repro.kernels`` implement the same math; the
XLA-blocked path here is the portable implementation used for the dry-run
(scan over q/k blocks keeps the working set and the HLO small at 32k+).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import PSpec, apply_rope, rms_norm
from repro.sharding import shard

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _fp8_quantize(x, dt):
    """Per-token-per-head symmetric quantization for fp8 KV caches: the
    head-dim amax maps onto the dtype's max normal, keeping small K/V
    values out of the fp8 subnormal range. Returns (quantized, scale)."""
    fmax = float(jnp.finfo(dt).max)
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / fmax, 1e-12)
    return (xf / s[..., None]).astype(dt), s


def allowed_mask(q_pos, k_pos, window=None, prefix_len=0):
    """bool (Sq, Sk): True where attention is allowed."""
    allowed = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        allowed &= k_pos[None, :] > (q_pos[:, None] - window)
    if prefix_len:
        allowed |= (k_pos[None, :] < prefix_len)
    return allowed


def attend_naive(q, k, v, q_pos, k_pos, scale, window=None, prefix_len=0):
    """q: (B,Sq,H,D), k/v: (B,Sk,Hkv,D). Returns (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[-1]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = allowed_mask(q_pos, k_pos, window, prefix_len)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, Dv).astype(q.dtype)


def attend_blocked(q, k, v, q_pos, k_pos, scale, window=None, prefix_len=0,
                   block_q=512, block_k=512, skip_noncausal=True):
    """Flash-style online-softmax attention expressed in XLA (scan over
    blocks).  With ``skip_noncausal`` the inner loop for q-block i only runs
    over k-blocks [0, i] (triangular), keeping compiled attention FLOPs near
    causal-optimal instead of 2x."""
    B, Sq, H, D = q.shape
    Sk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk

    qb = q.reshape(B, nq, bq, Hkv, G, D).astype(jnp.float32)
    kb = k.reshape(B, nk, bk, Hkv, D).astype(jnp.float32)
    vb = v.reshape(B, nk, bk, Hkv, Dv).astype(jnp.float32)
    qpb = q_pos.reshape(nq, bq)
    kpb = k_pos.reshape(nk, bk)

    def kv_step(carry, j, qi, qp):
        m, l, acc = carry
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qi, kb[:, j]) * scale
        mask = allowed_mask(qp, kpb[j], window, prefix_len)  # (bq, bk)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bqhgk,bkhd->bqhgd", p, vb[:, j])
        return (m_new, l, acc)

    def q_block(i):
        qi, qp = qb[:, i], qpb[i]
        m0 = jnp.full((B, bq, Hkv, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, bq, Hkv, G), jnp.float32)
        a0 = jnp.zeros((B, bq, Hkv, G, Dv), jnp.float32)
        if skip_noncausal and window is None and not prefix_len and nq == nk:
            m, l, acc = jax.lax.fori_loop(
                0, i + 1, lambda j, c: kv_step(c, j, qi, qp), (m0, l0, a0))
        else:
            (m, l, acc), _ = jax.lax.scan(
                lambda c, j: (kv_step(c, j, qi, qp), None), (m0, l0, a0),
                jnp.arange(nk))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    outs = jax.lax.map(q_block, jnp.arange(nq))  # (nq, B, bq, Hkv, G, D)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


def attend_decode_paged(q, k_pages, v_pages, block_table, valid_lens, scale,
                        layer=None):
    """One-token decode against a paged pool: q (B,1,H,D); pages
    (P,page_size,Hkv,D), or every layer's stacked (L,P,page_size,Hkv,D)
    read at ``layer``; block_table (B,N); valid_lens (B,). The Pallas
    kernel on TPU, the gather oracle elsewhere."""
    from repro import kernels as _k
    if _k.enabled():
        from repro.kernels import ops as _kops
        o = _kops.paged_decode_attention(q[:, 0], k_pages, v_pages,
                                         block_table, valid_lens, scale,
                                         layer=layer)
    else:
        from repro.kernels import ref as _kref
        o = _kref.paged_decode_attention(q[:, 0], k_pages, v_pages,
                                         block_table, valid_lens, scale,
                                         layer=layer)
    return o[:, None]


def attend_chunk_paged(q, k_pages, v_pages, block_table, start_pos, scale,
                       layer=None):
    """Page-aligned prefill chunk against the paged pool: q (B,T,H,D) —
    T fresh tokens already scattered into the pool — attends causally over
    everything the block table covers; start_pos (B,) absolute position of
    the chunk's first token; stacked pools are read at ``layer``. The
    Pallas kernel on TPU, the gather oracle elsewhere."""
    from repro import kernels as _k
    if _k.enabled():
        from repro.kernels import ops as _kops
        return _kops.chunked_prefill_attention(q, k_pages, v_pages,
                                               block_table, start_pos, scale,
                                               layer=layer)
    from repro.kernels import ref as _kref
    return _kref.chunked_prefill_attention(q, k_pages, v_pages, block_table,
                                           start_pos, scale, layer=layer)


def _at_layer(layer, *idx):
    """Scatter index into a paged pool: per-layer pools take ``idx``,
    stacked (L, ...) pools are written at ``layer`` in place."""
    return idx if layer is None else (layer, *idx)


def attend_decode(q, k_cache, v_cache, valid_len, scale):
    """One-token decode: q (B,1,H,D); caches (B,S,Hkv,D); valid_len scalar
    (number of filled slots; ring buffers pass their fill count)."""
    B, _, H, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    slot = jnp.arange(S)
    s = jnp.where(slot[None, None, None, :] < valid_len, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q.dtype)


# ------------------------------------------------------------------- block

def attn_table(cfg):
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    t = {
        "ln": PSpec((d,), (None,), "zeros"),
        "wq": PSpec((d, H * hd), (None, "heads")),
        "wk": PSpec((d, Hkv * hd), (None, "kv_heads")),
        "wv": PSpec((d, Hkv * hd), (None, "kv_heads")),
        "wo": PSpec((H * hd, d), ("heads", None)),
    }
    if cfg.qk_norm:
        t["q_norm"] = PSpec((hd,), (None,), "zeros")
        t["k_norm"] = PSpec((hd,), (None,), "zeros")
    return t


def attn_cache_spec(cfg, batch, max_len, window=None):
    """Returns {name: (shape, logical_axes)} for this block's decode cache.
    Mesh-aware: when kv_heads don't divide the model axis, the sequence dim
    is sharded instead (seq-sharded flash-decoding path)."""
    from repro.models.decode_sharded import seq_shard_axes, use_seq_sharded
    S = min(window, max_len) if window else max_len
    sh = (batch, S, cfg.num_kv_heads, cfg.head_dim)
    if use_seq_sharded(cfg.num_kv_heads, S):
        ax = seq_shard_axes()
    else:
        ax = ("batch", None, "kv_heads", None)
    return {"k": (sh, ax), "v": (sh, ax)}


def attn_apply(cfg, p, x, positions, *, mode, cache=None, window=None,
               use_blocked=True, triangular=True, block_table=None,
               dst_page=None, layer=None):
    """mode 'full' (train/prefill), 'chunk' (paged chunked prefill: x is
    (1, T, d) with T == page_size, positions a (T,) vector of absolute
    positions) or 'decode' (x is (B,1,d), positions is a scalar absolute
    position — or, for paged caches, a (B,) vector of per-sequence
    positions). Returns (x + attn_out, new_cache_or_None).

    A decode cache containing ``k_pages``/``v_pages`` (built by
    ``serving.kvpool.PagePool``) selects the paged path: the new token's
    K/V is scattered into its block-table page and attention gathers
    through ``block_table`` (B, N). Chunk mode scatters the whole chunk's
    K/V onto ``dst_page`` (the reserved scratch page when the chunk is
    prefix-shared and its real page already holds identical K/V) before
    gathering. With ``layer`` set, the paged leaves are every layer's
    stacked pool and only that layer's pages are written and read."""
    B = x.shape[0]
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = hd ** -0.5
    h = rms_norm(x, p["ln"])
    q = jnp.einsum("bsd,dq->bsq", h, p["wq"]).reshape(B, -1, H, hd)
    k = jnp.einsum("bsd,dq->bsq", h, p["wk"]).reshape(B, -1, Hkv, hd)
    v = jnp.einsum("bsd,dq->bsq", h, p["wv"]).reshape(B, -1, Hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)

    if mode == "full":
        S = x.shape[1]
        pos = positions  # (S,)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        prefix_len = cfg.num_prefix_tokens if cfg.prefix_lm else 0
        from repro import kernels as _k
        if (_k.enabled() and window is None and not prefix_len
                and S % 128 == 0 and hd % 8 == 0 and triangular):
            from repro.kernels import ops as _kops
            o = _kops.flash_attention(q, k, v, scale)
        elif use_blocked and S > 1024:
            o = attend_blocked(q, k, v, pos, pos, scale, window, prefix_len,
                               skip_noncausal=triangular)
        else:
            o = attend_naive(q, k, v, pos, pos, scale, window, prefix_len)
        new_cache = None
        if cache is not None:
            W = cache["k"].shape[1]
            if "k_scale" in cache:  # fp8 cache: quantize on write
                kd, ks = _fp8_quantize(k, cache["k"].dtype)
                vd, vs = _fp8_quantize(v, cache["v"].dtype)
            else:
                kd = k.astype(cache["k"].dtype)
                vd = v.astype(cache["v"].dtype)
            if W >= S:
                new_k = jax.lax.dynamic_update_slice(cache["k"], kd, (0, 0, 0, 0))
                new_v = jax.lax.dynamic_update_slice(cache["v"], vd, (0, 0, 0, 0))
            else:  # windowed cache: keep the last W tokens
                new_k, new_v = kd[:, -W:], vd[:, -W:]
            new_cache = {"k": new_k, "v": new_v}
            if "k_scale" in cache:
                if W >= S:
                    new_cache["k_scale"] = jax.lax.dynamic_update_slice(
                        cache["k_scale"], ks, (0, 0, 0))
                    new_cache["v_scale"] = jax.lax.dynamic_update_slice(
                        cache["v_scale"], vs, (0, 0, 0))
                else:
                    new_cache["k_scale"] = ks[:, -W:]
                    new_cache["v_scale"] = vs[:, -W:]
    elif mode == "chunk":  # page-aligned prefill chunks into the paged pool
        pos = positions          # (B,T) absolute positions, one row per run
        S = x.shape[1]
        ps = cache["k_pages"].shape[-3]
        assert S % ps == 0, (
            f"chunk mode is whole pool pages, got {S} tokens "
            f"(page_size {ps})")
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        kd = k.astype(cache["k_pages"].dtype)
        vd = v.astype(cache["v_pages"].dtype)
        # write the fresh chunks' K/V onto their pages BEFORE the gather so
        # each chunk attends to itself — and to pages other rows of the
        # SAME dispatch wrote at this layer — through the block table like
        # any other context; dst_page entries == scratch (0) mask the
        # write for prefix-shared pages (their pool page already holds it)
        # and for padding rows/chunks (the scratch page is write-only
        # garbage that causal masking keeps out of every real row)
        C = S // ps
        dst = dst_page if dst_page.ndim == 2 else dst_page[None]  # (B,C)
        at = _at_layer(layer, dst.reshape(-1))
        new_kp = cache["k_pages"].at[at].set(
            kd.reshape(B * C, ps, *kd.shape[2:]))
        new_vp = cache["v_pages"].at[at].set(
            vd.reshape(B * C, ps, *vd.shape[2:]))
        o = attend_chunk_paged(q, new_kp, new_vp, block_table, pos[:, 0],
                               scale, layer)
        new_cache = {"k_pages": new_kp, "v_pages": new_vp}
    elif "k_pages" in cache:  # decode against the paged pool
        pos = positions          # scalar or (B,) absolute positions
        posb = jnp.zeros((B,), jnp.int32) + pos
        q = apply_rope(q, posb[:, None], cfg.rope_theta)
        k = apply_rope(k, posb[:, None], cfg.rope_theta)
        ps = cache["k_pages"].shape[-3]
        kd = k.astype(cache["k_pages"].dtype)
        vd = v.astype(cache["v_pages"].dtype)
        # scatter the new token into each sequence's current page; inactive
        # slots carry all-zero block tables, landing on the scratch page
        pi = block_table[jnp.arange(B), posb // ps]
        at = _at_layer(layer, pi, posb % ps)
        new_kp = cache["k_pages"].at[at].set(kd[:, 0])
        new_vp = cache["v_pages"].at[at].set(vd[:, 0])
        o = attend_decode_paged(q, new_kp, new_vp, block_table, posb + 1,
                                scale, layer)
        new_cache = {"k_pages": new_kp, "v_pages": new_vp}
    else:  # decode
        from repro.models.decode_sharded import (seq_sharded_decode,
                                                 use_seq_sharded)
        pos = positions  # scalar int32
        posv = jnp.zeros((1,), jnp.int32) + pos
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)
        quant = "k_scale" in cache  # fp8 cache: quantize on write
        if quant:
            kd, ks = _fp8_quantize(k, cache["k"].dtype)
            vd, vs = _fp8_quantize(v, cache["v"].dtype)
        else:
            kd = k.astype(cache["k"].dtype)
            vd = v.astype(cache["v"].dtype)
        if not quant and use_seq_sharded(cfg.num_kv_heads,
                                         cache["k"].shape[1]):
            new_k, new_v, o = seq_sharded_decode(
                cache["k"], cache["v"], kd, vd, q, pos, window, scale)
            new_cache = {"k": new_k, "v": new_v}
        else:
            W = cache["k"].shape[1]
            slot = (pos % W) if window else jnp.minimum(pos, W - 1)
            new_k = jax.lax.dynamic_update_slice(cache["k"], kd, (0, slot, 0, 0))
            new_v = jax.lax.dynamic_update_slice(cache["v"], vd, (0, slot, 0, 0))
            valid = jnp.minimum(pos + 1, W)
            new_cache = {"k": new_k, "v": new_v}
            if quant:
                new_cache["k_scale"] = jax.lax.dynamic_update_slice(
                    cache["k_scale"], ks, (0, slot, 0))
                new_cache["v_scale"] = jax.lax.dynamic_update_slice(
                    cache["v_scale"], vs, (0, slot, 0))
                k_att = new_k.astype(jnp.float32) \
                    * new_cache["k_scale"][..., None]
                v_att = new_v.astype(jnp.float32) \
                    * new_cache["v_scale"][..., None]
            else:
                k_att, v_att = new_k, new_v
            o = attend_decode(q, k_att, v_att, valid, scale)

    o = shard(o, "batch", None, "heads", None)
    y = jnp.einsum("bsq,qd->bsd", o.reshape(B, o.shape[1], H * hd), p["wo"])
    return x + y, new_cache
