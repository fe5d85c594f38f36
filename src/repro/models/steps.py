"""Step functions: train_step, prefill_step, serve_step (single decode token).

These are the functions the launcher jits and the dry-run lowers; the
serving engine and the training loop both consume them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.sharding import shard
from repro.training import optim as _optim


def cross_entropy(logits, labels, mask=None):
    """Mean token cross-entropy in fp32. labels (B,S); mask optional."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn(model, params, batch, remat=False):
    cfg = model.cfg
    kw = {}
    if cfg.frontend == "audio":
        kw["embeddings"] = batch["embeddings"]
    elif cfg.frontend == "vision":
        kw["embeddings"] = batch["embeddings"]
        kw["tokens"] = batch["tokens"]
    else:
        kw["tokens"] = batch["tokens"]
    logits, _, aux = model.forward(params, mode="full", remat=remat,
                                   triangular=False, **kw)
    labels = batch["labels"]
    P = logits.shape[1] - labels.shape[1]
    if P > 0:  # vlm: no loss on the image prefix
        logits = logits[:, P:]
    loss = cross_entropy(logits[:, :-1], labels[:, 1:],
                         batch.get("mask", None))
    total = loss + cfg.router_aux_coef * aux
    return total, {"ce": loss, "aux": aux}


def make_train_step(model, opt_cfg: _optim.AdamWConfig, remat=True):
    def train_step(params, opt_state, batch):
        batch = {k: shard(v, "batch", *([None] * (v.ndim - 1)))
                 for k, v in batch.items()}
        (loss, parts), grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch, remat=remat), has_aux=True
        )(params)
        new_params, new_state, stats = _optim.apply_updates(
            opt_cfg, params, grads, opt_state)
        metrics = {"loss": loss, **parts, **stats}
        return new_params, new_state, metrics

    return train_step


def make_prefill_step(model, max_len=None, window=None, cache_dtype=jnp.bfloat16):
    """Returns fn(params, cache, inputs_dict) -> (last_logits, cache)."""
    def prefill_step(params, cache, inputs):
        logits, new_cache, _ = model.forward(
            params, mode="full", cache=cache, window=window, **inputs)
        return logits[:, -1, :], new_cache

    return prefill_step


def make_serve_step(model, window=None):
    """One decode token against a KV/state cache."""
    def serve_step(params, cache, token, pos):
        logits, new_cache, _ = model.forward(
            params, mode="decode", tokens=token, cache=cache, pos=pos,
            window=window)
        return logits[:, 0, :], new_cache

    return serve_step


def make_chunked_prefill_step(model, window=None):
    """One page-aligned prefill chunk against the paged KV pool: token
    (1, C*page_size) ids for C consecutive whole pages (zero-padded past
    the prompt), ``start`` scalar absolute position of the chunk's first
    token, block_table (1, N) physical page ids covering every page the
    sequence occupies through this chunk, ``dst_page`` (C,) page ids the
    chunk's K/V lands on — an entry equal to the reserved scratch page
    masks the write for a prefix-shared page that already holds identical
    K/V. Returns the chunk's full logits (1, C*page_size, V) — callers
    index the prompt-boundary row — plus the updated pool cache."""
    def chunked_prefill_step(params, cache, token, start, block_table,
                             dst_page):
        logits, new_cache, _ = model.forward(
            params, mode="chunk", tokens=token, cache=cache, pos=start,
            window=window, block_table=block_table, dst_page=dst_page)
        return logits, new_cache

    return chunked_prefill_step


def make_fused_prefill_step(model, window=None):
    """One fused dispatch for EVERY prefill chunk run of a batcher tick,
    with on-device first-token emission (the fused-tick fast path).

    Rows are independent chunk runs, possibly from different requests:
    token (R, C*page_size) zero-padded ids, ``start`` (R,) absolute
    positions of each run's first token, block_table (R, W), dst_page
    (R, C) pool page ids (scratch page == masked write, used for
    prefix-shared chunks and padding). Runs whose request completes its
    prompt this dispatch emit their boundary argmax token straight into
    the device-resident sampling state: ``emit_slot`` (R,) is the decode
    slot to write (num_slots = no emission, dropped OOB), ``emit_off``
    (R,) the boundary row inside the run, ``gen_idx`` (R,) the write
    index into ``gen_buf``. Returns (new_last_tok, new_gen_buf,
    new_cache) — no logits leave the device, so the host never syncs."""
    def fused_prefill_step(params, cache, token, start, block_table,
                           dst_page, emit_slot, emit_off, gen_idx,
                           last_tok, gen_buf):
        logits, new_cache, _ = model.forward(
            params, mode="chunk", tokens=token, cache=cache, pos=start,
            window=window, block_table=block_table, dst_page=dst_page)
        with jax.named_scope("emit"):
            rows = jnp.arange(logits.shape[0])
            bound = jnp.argmax(logits[rows, emit_off],
                               axis=-1).astype(jnp.int32)
            new_last = last_tok.at[emit_slot].set(bound, mode="drop")
            new_gen = gen_buf.at[emit_slot, gen_idx].set(bound, mode="drop")
        return new_last, new_gen, new_cache

    return fused_prefill_step


def make_paged_serve_step(model, window=None):
    """One fused decode step for ALL sequences of a paged KV pool: token
    (B,1), pos (B,) per-sequence absolute positions, block_table (B,N)
    physical page ids. The cache pytree holds the pool's shared
    ``k_pages``/``v_pages`` leaves (see serving.kvpool.PagePool)."""
    def paged_serve_step(params, cache, token, pos, block_table):
        logits, new_cache, _ = model.forward(
            params, mode="decode", tokens=token, cache=cache, pos=pos,
            window=window, block_table=block_table)
        return logits[:, 0, :], new_cache

    return paged_serve_step


def make_fused_decode_step(model, window=None):
    """Paged decode over all slots against DEVICE-RESIDENT sampling state
    (the fused-tick fast path): each row's input token comes from
    ``host_tok`` where ``host_mask`` is set (admission-seeded or
    host-sampled tokens) and from ``last_tok`` otherwise (tokens the
    device produced in earlier dispatches and the host never saw).
    Greedy next tokens are written back into ``last_tok`` and logged at
    ``gen_buf[write_slot, gen_idx]`` — rows with write_slot == num_slots
    (idle, stalled, or host-sampled slots) drop their writes OOB.
    Returns (logits, new_last_tok, new_gen_buf, new_cache); greedy
    callers ignore the logits, so nothing forces a device sync."""
    def fused_decode_step(params, cache, last_tok, host_mask, host_tok,
                          pos, block_table, write_slot, gen_idx, gen_buf):
        with jax.named_scope("embed"):
            tok = jnp.where(host_mask, host_tok, last_tok)[:, None]
        logits, new_cache, _ = model.forward(
            params, mode="decode", tokens=tok, cache=cache, pos=pos,
            window=window, block_table=block_table)
        with jax.named_scope("emit"):
            logits = logits[:, 0, :]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            new_last = last_tok.at[write_slot].set(nxt, mode="drop")
            new_gen = gen_buf.at[write_slot, gen_idx].set(nxt, mode="drop")
        return logits, new_last, new_gen, new_cache

    return fused_decode_step
