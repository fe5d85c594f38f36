"""Sequence-sharded decode attention (flash-decoding over chips).

When a model's kv_heads do not divide the model axis (GQA kv=1/2/3/8 on a
16-way axis, or MLA's headless latent cache), replicating the KV cache per
chip is hopeless at 32k-524k contexts. Instead the cache's *sequence* dim is
sharded over the model axis and decode attention runs under shard_map:

  - the rank owning slot ``pos`` writes the new K/V (one-slot predicated DUS)
  - every rank computes partial scores over its local slots
  - partials merge with a log-sum-exp combine: pmax(max), psum(denominator),
    psum(weighted values)

Collectives per layer: two scalar-ish all-reduces (B,Hkv,G) and one
(B,Hkv,G,Dv) all-reduce — O(B*H*D) bytes instead of an O(S) gather.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.attention import NEG_INF
from repro.sharding import current_mesh


def use_seq_sharded(kv_heads: int, seq_len: int | None = None) -> bool:
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.shape or mesh.shape["model"] == 1:
        return False
    if seq_len is not None and seq_len % mesh.shape["model"] != 0:
        return False  # cache too short/ragged to seq-shard
    return kv_heads == 0 or kv_heads % mesh.shape["model"] != 0


def seq_shard_axes():
    """Logical axes for a seq-sharded KV cache entry (B,S,Hkv,D)."""
    return ("batch", "kv_seq", None, None)


def _inner(kc, vc, kn, vn, q, slot, valid, *, scale, model_axis):
    B, S_loc, Hkv, Dk = kc.shape
    Dv = vc.shape[-1]
    H = q.shape[2]
    G = H // Hkv
    r = jax.lax.axis_index(model_axis)
    lp = slot - r * S_loc
    own = (lp >= 0) & (lp < S_loc)
    lpc = jnp.clip(lp, 0, S_loc - 1)
    old_k = jax.lax.dynamic_slice(kc, (0, lpc, 0, 0), kn.shape)
    old_v = jax.lax.dynamic_slice(vc, (0, lpc, 0, 0), vn.shape)
    kc = jax.lax.dynamic_update_slice(
        kc, jnp.where(own, kn, old_k), (0, lpc, 0, 0))
    vc = jax.lax.dynamic_update_slice(
        vc, jnp.where(own, vn, old_v), (0, lpc, 0, 0))

    qg = q.reshape(B, Hkv, G, Dk).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, kc.astype(jnp.float32)) * scale
    gslot = r * S_loc + jnp.arange(S_loc)
    s = jnp.where(gslot[None, None, None, :] < valid, s, NEG_INF)
    m_loc = s.max(axis=-1)
    m = jax.lax.pmax(m_loc, model_axis)
    p = jnp.exp(s - m[..., None])
    l = jax.lax.psum(p.sum(axis=-1), model_axis)
    num = jax.lax.psum(
        jnp.einsum("bhgk,bkhd->bhgd", p, vc.astype(jnp.float32)), model_axis)
    o = (num / jnp.maximum(l, 1e-30)[..., None]).reshape(B, 1, H, Dv)
    return kc, vc, o.astype(q.dtype)


def seq_sharded_decode(k_cache, v_cache, k_new, v_new, q, pos, window,
                       scale):
    """k_cache/v_cache (B,S,Hkv,Dk/Dv) with S sharded on 'model';
    k_new/v_new (B,1,Hkv,D*); q (B,1,H,Dk); pos scalar int32.
    Returns (new_k_cache, new_v_cache, out (B,1,H,Dv))."""
    mesh = current_mesh()
    B, S = k_cache.shape[0], k_cache.shape[1]
    W = S
    slot = (pos % W) if window else jnp.minimum(pos, W - 1)
    valid = jnp.minimum(pos + 1, W)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bsz = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    if not batch_axes or B % bsz != 0:
        batch_axes = ()
    bspec = batch_axes if batch_axes else None
    cache_spec = P(bspec, "model", None, None)
    new_spec = P(bspec, None, None, None)
    fn = partial(_inner, scale=scale, model_axis="model")
    return jax.shard_map(
        fn, mesh=mesh, check_vma=False,
        in_specs=(cache_spec, cache_spec, new_spec, new_spec, new_spec,
                  P(), P()),
        out_specs=(cache_spec, cache_spec, new_spec),
    )(k_cache, v_cache, k_new, v_new, q,
      jnp.asarray(slot, jnp.int32), jnp.asarray(valid, jnp.int32))
