"""Paged GQA decode attention Pallas TPU kernel: ONE query token per
sequence, K/V gathered through a per-sequence block table over a shared
page pool.

Layout (vLLM-style): the pool holds ``(num_pages, page_size, Hkv, D)`` K
and V arrays shared by every sequence — or all layers' pools stacked as
``(L, num_pages, page_size, Hkv, D)`` with ``layer`` naming the one to
read, so a scan over layers never copies a layer's pool out of the stack
(``layer`` is a third scalar-prefetch operand). ``block_table[b, n]``
names the physical page backing logical positions
``[n*page_size, (n+1)*page_size)`` of sequence ``b``. The block table and
per-sequence ``valid_lens`` arrive via scalar prefetch (SMEM), so each
grid step's page index is known before its DMA issues.

Grid (B, n_pages_per_seq): each program fetches one *logical* page with
ALL its KV heads — a ``(page_size, Hkv, D)`` block, whose last two dims
are the pool's own, as Mosaic's tiling requires — and attends every query
head against it, looping over the KV heads inside the kernel. The
online-softmax state of every head lives in VMEM scratch across the
sequential page dimension. Whole pages past a sequence's fill level are
predicated off, so decode cost tracks live tokens, not table capacity;
unallocated table entries point at the reserved scratch page (id 0) and
are both masked *and* skipped.

page_size defaults to 16 rows — small DMAs, but at decode batch sizes the
gather is latency- not bandwidth-bound, and small pages are what make
prefix sharing granular enough to matter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(bt_ref, valid_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, ps, npages, hkv):
    b = pl.program_id(0)
    ni = pl.program_id(1)
    valid = valid_ref[b]

    @pl.when(ni == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32)          # (G, D)
            k = k_ref[0, 0, :, h, :].astype(jnp.float32)  # (ps, D)
            v = v_ref[0, 0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
            slot = ni * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(slot < valid, s, NEG_INF)
            m_prev = m_ref[h]                            # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
            m_ref[h] = m_new

    # skip whole logical pages past this sequence's fill level
    pl.when(ni * ps < valid)(_compute)

    @pl.when(ni == npages - 1)
    def _final():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_table, valid_lens,
                           scale=None, layer=None, interpret=True):
    """q (B, H, D) one token per sequence; k_pages/v_pages
    (P, page_size, Hkv, D) shared pool, or (L, P, page_size, Hkv, D)
    stacked pools read at int32 ``layer``; block_table (B, N) int32
    physical page ids; valid_lens (B,) int32 filled tokens per sequence.
    Returns (B, H, D)."""
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    B, H, D = q.shape
    _, P, ps, Hkv, _ = k_pages.shape
    N = block_table.shape[1]
    G = H // Hkv
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, Hkv, G, D)
    bt = jnp.asarray(block_table, jnp.int32)
    valid = jnp.asarray(valid_lens, jnp.int32).reshape(B)
    ly = jnp.asarray(layer, jnp.int32).reshape(1)

    kern = functools.partial(_kernel, scale=scale, ps=ps, npages=N, hkv=Hkv)

    def page(b, ni, bt, vl, ly):
        return (ly[0], bt[b, ni], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, N),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D),
                         lambda b, ni, bt, vl, ly: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, ps, Hkv, D), page),
            pl.BlockSpec((1, 1, ps, Hkv, D), page),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D),
                               lambda b, ni, bt, vl, ly: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(bt, valid, ly, qg, k_pages, v_pages)
    return out.reshape(B, H, D)
