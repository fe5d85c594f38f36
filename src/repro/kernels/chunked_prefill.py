"""Chunked-prefill GQA attention Pallas TPU kernel: a page-aligned chunk
of Q tokens per sequence attends causally over ALL prior context (earlier
prompt pages + the chunk itself) gathered through a per-sequence block
table over a shared page pool.

This is the prefill half of the paged serving stack: where
``paged_attention.py`` advances ONE decode token per sequence, this kernel
advances a whole chunk of ``T`` fresh prompt tokens whose K/V has already
been scattered into the chunk's pool page(s). Because context arrives
through the block table, a prefix-sharing batcher can skip recomputing
chunks whose pages it attached to — the following chunk simply gathers the
shared pages like any other context.

Layout matches ``paged_attention.py`` (vLLM-style): ``k_pages``/``v_pages``
are ``(num_pages, page_size, Hkv, D)`` shared by every sequence (or all
layers' pools stacked, read at ``layer``); ``block_table[b, n]`` names the
physical page backing logical positions ``[n*page_size, (n+1)*page_size)``
of sequence ``b``; ``start_pos[b]`` is the absolute position of the
chunk's first token. Both arrive via scalar prefetch (SMEM) so each grid
step's page index is known before its DMA issues.

Grid (B, q_tiles, n_pages): the page dimension is innermost and
sequential. Each step fetches one page with ALL its KV heads (a
``(page_size, Hkv, D)`` block, whose last two dims are the pool's own, as
Mosaic's tiling requires) and loops over the KV heads inside the kernel.
The wrapper lays q out head-major, ``(B, Hkv, G, T, D)``, so one KV head's
query rows of a q-tile are a single ``(G * block_q, D)`` matrix. The
online-softmax state (m, l, acc) of every head lives in VMEM scratch per
q-tile — the same blocking scheme as ``flash_attention.py`` with the page
gather replacing the contiguous k-block index map. Causality is enforced
per (q row, k slot) against absolute positions, and whole pages strictly
in the causal future of a q-tile are predicated off, so chunk cost tracks
context actually attended, not table capacity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# Query rows (q-tile tokens x query heads) one grid step holds: bounds the
# f32 accumulator at rows * D * 4 bytes (1 MiB at D=128) so the kernel
# stays well inside the default scoped VMEM at any chunk length.
MAX_TILE_ROWS = 2048


def _kernel(bt_ref, start_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, ps, bq, npages, hkv, g):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ni = pl.program_id(2)
    start = start_ref[b]

    @pl.when(ni == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        # row r of a head's (G*bq, D) query matrix is token r % bq
        qpos = start + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (g, bq, ps), 1).reshape(g * bq, ps)
        kpos = ni * ps + jax.lax.broadcasted_iota(jnp.int32, (g * bq, ps), 1)
        causal = kpos <= qpos
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32).reshape(g * bq, -1)
            k = k_ref[0, 0, :, h, :].astype(jnp.float32)  # (ps, D)
            v = v_ref[0, 0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
            s = jnp.where(causal, s, NEG_INF)
            m_prev = m_ref[h]                            # (G*bq, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
            m_ref[h] = m_new

    # skip whole logical pages strictly in this q-tile's causal future
    pl.when(ni * ps <= start + qi * bq + bq - 1)(_compute)

    @pl.when(ni == npages - 1)
    def _final():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = o.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def chunked_prefill_attention(q, k_pages, v_pages, block_table, start_pos,
                              scale=None, block_q=None, layer=None,
                              interpret=True):
    """q (B, T, H, D) — T fresh tokens per sequence, token t at absolute
    position ``start_pos[b] + t``; k_pages/v_pages (P, page_size, Hkv, D)
    shared pool ALREADY holding the chunk's own K/V (or (L, P, page_size,
    Hkv, D) stacked pools read at int32 ``layer``); block_table (B, N)
    int32 physical page ids covering positions [0, start+T); start_pos
    (B,) int32. ``block_q`` (default: the largest divisor of T that keeps
    a tile within ``MAX_TILE_ROWS`` query rows) must divide T. Returns
    (B, T, H, D)."""
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    B, T, H, D = q.shape
    _, P, ps, Hkv, _ = k_pages.shape
    N = block_table.shape[1]
    G = H // Hkv
    scale = scale if scale is not None else D ** -0.5
    if block_q is None:
        block_q = max([d for d in range(1, T + 1)
                       if T % d == 0 and d * H <= MAX_TILE_ROWS] or [1])
    bq = min(block_q, T)
    assert T % bq == 0, f"chunk len {T} not a multiple of q tile {bq}"
    nq = T // bq
    qg = q.reshape(B, T, Hkv, G, D).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,T,D)
    bt = jnp.asarray(block_table, jnp.int32)
    start = jnp.asarray(start_pos, jnp.int32).reshape(B)
    ly = jnp.asarray(layer, jnp.int32).reshape(1)

    kern = functools.partial(_kernel, scale=scale, ps=ps, bq=bq, npages=N,
                             hkv=Hkv, g=G)

    def page(b, qi, ni, bt, sp, ly):
        return (ly[0], bt[b, ni], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nq, N),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, bq, D),
                         lambda b, qi, ni, bt, sp, ly: (b, 0, 0, qi, 0)),
            pl.BlockSpec((1, 1, ps, Hkv, D), page),
            pl.BlockSpec((1, 1, ps, Hkv, D), page),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, bq, D),
                               lambda b, qi, ni, bt, sp, ly:
                               (b, 0, 0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G * bq, 1), jnp.float32),
            pltpu.VMEM((Hkv, G * bq, 1), jnp.float32),
            pltpu.VMEM((Hkv, G * bq, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, T, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="chunked_prefill_attention",
    )(bt, start, ly, qg, k_pages, v_pages)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, D)
