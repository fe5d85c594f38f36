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
per-sequence ``valid_lens`` arrive via scalar prefetch (SMEM), so every
page id is known before its copy issues.

Two kernels, chosen by the head width; both mask positions at or past
the fill level, and the reserved scratch page (id 0) that unallocated
table entries name, to exact zeros.

Blocked (``D`` a multiple of the 128 lanes: every Qwen3 layout). The
kernel works a block of ``ppb`` pages at a time (``block_pages``: 256
tokens at 16-token pages, at most the table's width) and only on the
pages a sequence holds. Grid (B,): one program per sequence, run in
order. The pool stays in HBM (``memory_space=ANY``), viewed as
``(L, P, page_size * Hkv, D)`` — a bitcast of the stacked pool, since
``D`` fills whole lane tiles — and the kernel copies K and V itself, one
``make_async_copy`` per page into a double-buffered VMEM scratch of
``(2, ppb * page_size * Hkv, D)``. A sequence walks only its live blocks,
``ceil(valid / (ppb * page_size))`` of them (a loop bound read from
SMEM), and within a block copies only the pages that hold a position
below its fill level: work follows live tokens, not ``B x N``, and a
sequence with ``valid_lens`` 0 copies and computes nothing. While one
block is computed, the next live block's copies are in flight: the
sequence's next block, or else the first block of the next sequence that
has one, so the prefetch crosses programs. Each block attends all query
heads at once: one ``(H, D) x (D, ppb * page_size * Hkv)`` product of the
cached dtype accumulated in f32, whose columns of another KV head than
the query row's are masked, so no head is sliced out of the block. The
online-softmax state, the probabilities and the ``p @ v`` accumulation
stay f32.

Per page (narrower heads, e.g. ``D`` 64). Viewing such a pool as rows of
``D`` is not a bitcast, and Mosaic will not copy a ``(page_size, Hkv, D)``
page by hand, so the pipeline fetches pages: grid (B, n_pages_per_seq),
each step one logical page with all its KV heads (the pool's own last two
dims, as Mosaic's tiling requires), the KV heads looped inside the
kernel, the online-softmax state in VMEM scratch across the page
dimension. Pages past a sequence's fill level skip their compute, not
their grid step.

page_size defaults to 16 rows: small pages are what make prefix sharing
granular enough to matter, and the blocked kernel gathers enough of them
that small pages no longer cost a grid step each.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# Tokens one block of the blocked kernel aims to hold.
BLOCK_TOKENS = 256


def block_pages(ps, n_pages):
    """Pages a block holds: ``BLOCK_TOKENS`` tokens, at least one page,
    at most the table's width."""
    return min(max(1, BLOCK_TOKENS // ps), n_pages)


def _blocked_kernel(bt_ref, valid_ref, layer_ref, q_ref, k_hbm, v_hbm,
                    o_ref, k_buf, v_buf, sems, state, m_ref, l_ref, acc_ref,
                    *, scale, ps, ppb, npages, hkv, group):
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    bk = ppb * ps                       # tokens a block holds
    rp = ps * hkv                       # buffer rows a page takes
    layer = layer_ref[0]

    def fill(row):
        return jnp.minimum(valid_ref[row], npages * ps)

    def page_copies(row, blk, slot, op):
        """Start (or wait for) the copies of the pages of block ``blk`` of
        ``row`` that hold a live position, into buffer ``slot``."""
        for j in range(ppb):
            n = blk * ppb + j

            @pl.when(n * ps < fill(row))
            def _page(n=n, j=j):
                pid = bt_ref[row, n]
                for i, (src, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    cp = pltpu.make_async_copy(
                        src.at[layer, pid], buf.at[slot, pl.ds(j * rp, rp)],
                        sems.at[i, slot])
                    if op == "start":
                        cp.start()
                    else:
                        cp.wait()

    def next_block(blk):
        """The live block after ``(b, blk)`` in grid order: the row's
        next block, else block 0 of the next row with a live position;
        the row is ``rows`` when none is left."""
        def next_row():
            r = jax.lax.fori_loop(
                b + 1, rows,
                lambda _, r: jnp.where(fill(r) == 0, r + 1, r), b + 1)
            return r, 0
        return jax.lax.cond((blk + 1) * bk < fill(b),
                            lambda: (b, blk + 1), next_row)

    @pl.when(b == 0)
    def _first():
        state[0] = 0            # buffer that holds the next block
        state[1] = 0            # 1 while that block's copies are in flight
        # slots of pages a block does not copy are masked, but 0 * v
        # must stay 0: never let them hold uninitialised (non-finite) bits
        v_buf[...] = jnp.zeros_like(v_buf)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    valid = fill(b)
    q = q_ref[0]                        # (H, D)
    # score (h, c) pairs query head h with the block's row c: KV head
    # c % hkv of position c // hkv
    shape = (q.shape[0], bk * hkv)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    own = col % hkv == jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group

    def block(blk, carry):
        slot = state[0]

        @pl.when(state[1] == 0)
        def _own():
            page_copies(b, blk, slot, "start")

        nrow, nblk = next_block(blk)
        more = nrow < rows

        @pl.when(more)
        def _prefetch():
            page_copies(nrow, nblk, 1 - slot, "start")

        state[0] = 1 - slot
        state[1] = more.astype(jnp.int32)
        page_copies(b, blk, slot, "wait")
        s = jax.lax.dot_general(q, k_buf[slot], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(own & (col < (valid - blk * bk) * hkv), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_buf[slot].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, (valid + bk - 1) // bk, block, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


def _blocked(q, k_pages, v_pages, bt, valid, ly, scale, interpret):
    B, H, D = q.shape
    L, P, ps, Hkv, _ = k_pages.shape
    N = bt.shape[1]
    ppb = block_pages(ps, N)
    rp = ps * Hkv
    # a page's (page_size, Hkv, D) as rp rows of one head vector each
    pools = [x.reshape(L, P, rp, D) for x in (k_pages, v_pages)]
    qrow = pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[qrow, pool, pool],
        out_specs=qrow,
        scratch_shapes=[
            pltpu.VMEM((2, ppb * rp, D), k_pages.dtype),
            pltpu.VMEM((2, ppb * rp, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_blocked_kernel, scale=scale, ps=ps, ppb=ppb,
                          npages=N, hkv=Hkv, group=H // Hkv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        # the prefetch carries a block's copies from one program to the
        # next, so the programs run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(bt, valid, ly, q, *pools)


def _per_page_kernel(bt_ref, valid_ref, layer_ref, q_ref, k_ref, v_ref,
                     o_ref, m_ref, l_ref, acc_ref, *, scale, ps, npages, hkv):
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


def _per_page(q, k_pages, v_pages, bt, valid, ly, scale, interpret):
    B, H, D = q.shape
    _, _, ps, Hkv, _ = k_pages.shape
    N = bt.shape[1]
    G = H // Hkv

    def page(b, ni, bt, vl, ly):
        return (ly[0], bt[b, ni], 0, 0, 0)

    heads = pl.BlockSpec((1, Hkv, G, D), lambda b, ni, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, N),
        in_specs=[heads, pl.BlockSpec((1, 1, ps, Hkv, D), page),
                  pl.BlockSpec((1, 1, ps, Hkv, D), page)],
        out_specs=heads,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_per_page_kernel, scale=scale, ps=ps, npages=N,
                          hkv=Hkv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(bt, valid, ly, q.reshape(B, Hkv, G, D), k_pages, v_pages)
    return out.reshape(B, H, D)


def paged_decode_attention(q, k_pages, v_pages, block_table, valid_lens,
                           scale=None, layer=None, interpret=True):
    """q (B, H, D) one token per sequence; k_pages/v_pages
    (P, page_size, Hkv, D) shared pool, or (L, P, page_size, Hkv, D)
    stacked pools read at int32 ``layer``; block_table (B, N) int32
    physical page ids; valid_lens (B,) int32 filled tokens per sequence.
    Returns (B, H, D); a sequence with no filled token gets zeros."""
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    D = q.shape[2]
    scale = scale if scale is not None else D ** -0.5
    bt = jnp.asarray(block_table, jnp.int32)
    valid = jnp.asarray(valid_lens, jnp.int32).reshape(q.shape[0])
    ly = jnp.asarray(layer, jnp.int32).reshape(1)
    kernel = _blocked if D % 128 == 0 else _per_page
    return kernel(q, k_pages, v_pages, bt, valid, ly, scale, interpret)
