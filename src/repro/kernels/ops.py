"""jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively (interpret=False); everywhere else they
run in interpret mode (kernel body executed in Python/XLA on the CPU), so
the same call sites validate without a chip. ``repro.models`` calls these
ops where ``repro.kernels.enabled()`` holds (a TPU backend) and the
portable XLA implementations elsewhere.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import chunked_prefill as _chunk
from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _paged
from repro.kernels import rglru as _rg
from repro.kernels import ssd as _ssd


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("scale", "causal", "block_q", "block_k"))
def flash_attention(q, k, v, scale=None, causal=True, block_q=128,
                    block_k=128):
    """q (B,Sq,H,D); k/v (B,Sk,Hkv,D) -> (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, -1, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, -1, D)
    o = _fa.flash_attention(qf, kf, vf, scale, causal, block_q, block_k,
                            interpret=_interpret())
    return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("scale", "block_k"))
def decode_attention(q, k, v, valid_len, scale=None, block_k=512):
    """q (B,H,D) one token; k/v (B,S,Hkv,D)."""
    return _dec.decode_attention(q, k, v, valid_len, scale, block_k,
                                 interpret=_interpret())


@partial(jax.jit, static_argnames=("scale",))
def paged_decode_attention(q, k_pages, v_pages, block_table, valid_lens,
                           scale=None, layer=None):
    """q (B,H,D) one token; k_pages/v_pages (P,page_size,Hkv,D) shared
    pool, or (L,P,page_size,Hkv,D) stacked pools read at ``layer``;
    block_table (B,N); valid_lens (B,)."""
    return _paged.paged_decode_attention(q, k_pages, v_pages, block_table,
                                         valid_lens, scale, layer=layer,
                                         interpret=_interpret())


@partial(jax.jit, static_argnames=("scale", "block_q"))
def chunked_prefill_attention(q, k_pages, v_pages, block_table, start_pos,
                              scale=None, block_q=None, layer=None):
    """q (B,T,H,D) one page-aligned prefill chunk per sequence;
    k_pages/v_pages (P,page_size,Hkv,D) shared pool (or stacked
    (L,P,page_size,Hkv,D) pools read at ``layer``) already holding the
    chunk's K/V; block_table (B,N); start_pos (B,) absolute chunk starts.

    Rows are independent — the fused tick batches chunk runs from
    DIFFERENT requests (with bucketed B/T/N, see batcher._bucket); pad
    rows point at the scratch page and their −∞-masked positions
    contribute exact zeros, so bucketing never perturbs real rows."""
    return _chunk.chunked_prefill_attention(q, k_pages, v_pages, block_table,
                                            start_pos, scale, block_q=block_q,
                                            layer=layer,
                                            interpret=_interpret())


@partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, a, B_, C_, chunk=256):
    """Mamba-2 SSD selective scan; see kernels.ssd for shapes."""
    return _ssd.ssd_full(x, dt, a, B_, C_, chunk, interpret=_interpret())


@partial(jax.jit, static_argnames=("block_seq", "block_lanes"))
def rglru_scan(a, b, h0=None, block_seq=256, block_lanes=512):
    """Linear recurrence h_t = a_t h_{t-1} + b_t over axis 1."""
    return _rg.rglru_scan(a, b, h0, block_seq, block_lanes,
                          interpret=_interpret())
