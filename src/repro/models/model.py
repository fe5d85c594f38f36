"""Model assembly: pattern-based decoder built from mixer blocks + (MoE|MLP).

Layers are stored *stacked* (one leading "groups" dim per repeating pattern
cycle) and executed with ``lax.scan`` so the HLO stays O(1) in depth — at
500+ device dry-runs and 61-layer models this is what keeps compile times
sane. Heterogeneous depth patterns (e.g. RecurrentGemma's rglru,rglru,attn)
scan over whole pattern cycles; non-uniform prefixes (MoE first-dense-layer)
and cycle remainders live outside the scan as individual layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention, mla, moe, rglru, ssm
from repro.models.layers import (PSpec, abstract_params, init_params,
                                 param_axes, rms_norm, stack_table,
                                 swiglu_apply, swiglu_table)
from repro.sharding import shard

BLOCK_TABLE = {
    "attn": attention.attn_table,
    "mla": mla.mla_table,
    "ssm": ssm.ssm_table,
    "rglru": rglru.rglru_table,
}
BLOCK_APPLY = {
    "attn": attention.attn_apply,
    "mla": mla.mla_apply,
    "ssm": ssm.ssm_apply,
    "rglru": rglru.rglru_apply,
}
BLOCK_CACHE = {
    "attn": attention.attn_cache_spec,
    "mla": mla.mla_cache_spec,
    "ssm": ssm.ssm_cache_spec,
    "rglru": rglru.rglru_cache_spec,
}


def effective_pattern(cfg):
    if cfg.use_mla:
        return ("mla",)
    return cfg.pattern


@dataclass(frozen=True)
class Segments:
    head: tuple      # layer indices before the scan (first_dense_layers)
    n_groups: int    # scanned pattern cycles
    tail: tuple      # layer indices after the scan


def segments(cfg) -> Segments:
    pat = effective_pattern(cfg)
    n0 = cfg.first_dense_layers
    body = cfg.num_layers - n0
    g = body // len(pat)
    rem = body % len(pat)
    return Segments(
        head=tuple(range(n0)),
        n_groups=g,
        tail=tuple(range(n0 + g * len(pat), cfg.num_layers)),
    )


def _layer_table(cfg, block_type, use_moe):
    t = {"mixer": BLOCK_TABLE[block_type](cfg)}
    if use_moe:
        t["moe"] = moe.moe_table(cfg)
    elif cfg.d_ff:
        t["mlp"] = {"ln": PSpec((cfg.d_model,), (None,), "zeros"),
                    **swiglu_table(cfg.d_model, cfg.d_ff)}
    return t


def _layer_block_type(cfg, idx):
    pat = effective_pattern(cfg)
    n0 = cfg.first_dense_layers
    if idx < n0:
        return pat[0]  # head layers use the base mixer, dense MLP
    return pat[(idx - n0) % len(pat)]


def _layer_uses_moe(cfg, idx):
    return cfg.num_experts > 0 and idx >= cfg.first_dense_layers


class Model:
    def __init__(self, cfg):
        self.cfg = cfg
        self.seg = segments(cfg)
        self.pattern = effective_pattern(cfg)

    # ------------------------------------------------------------- tables
    def param_table(self):
        cfg = self.cfg
        t = {"embed": PSpec((cfg.vocab_size, cfg.d_model), ("vocab", None),
                            scale=0.02),
             "final_norm": PSpec((cfg.d_model,), (None,), "zeros")}
        if not cfg.tie_embeddings:
            t["lm_head"] = PSpec((cfg.d_model, cfg.vocab_size),
                                 (None, "vocab"))
        for i in self.seg.head:
            t[f"head_{i}"] = _layer_table(
                cfg, _layer_block_type(cfg, i), use_moe=False)
        if self.seg.n_groups:
            group = {}
            for s, bt in enumerate(self.pattern):
                li = cfg.first_dense_layers + s
                group[f"slot{s}"] = _layer_table(
                    cfg, bt, _layer_uses_moe(cfg, li))
            t["blocks"] = stack_table(group, self.seg.n_groups)
        for j, i in enumerate(self.seg.tail):
            t[f"tail_{j}"] = _layer_table(
                cfg, _layer_block_type(cfg, i), _layer_uses_moe(cfg, i))
        return t

    def init(self, key, dtype=None):
        return init_params(self.param_table(), key,
                           dtype or self.cfg.dtype)

    def abstract(self, dtype=None):
        return abstract_params(self.param_table(), dtype or self.cfg.dtype)

    def axes(self):
        return param_axes(self.param_table())

    # -------------------------------------------------------------- cache
    def _layer_cache_spec(self, idx, batch, max_len, window):
        bt = _layer_block_type(self.cfg, idx)
        w = window if bt == "attn" else None
        if bt == "attn":
            eff_w = w or self.cfg.attn_window
            return {"mixer": BLOCK_CACHE[bt](self.cfg, batch, max_len, eff_w)}
        if bt == "mla":
            return {"mixer": BLOCK_CACHE[bt](self.cfg, batch, max_len)}
        return {"mixer": BLOCK_CACHE[bt](self.cfg, batch)}

    def cache_spec(self, batch, max_len, window=None):
        """{segment: {name: (shape, axes)}} mirroring the param layout."""
        spec = {}
        for i in self.seg.head:
            spec[f"head_{i}"] = self._layer_cache_spec(i, batch, max_len, window)
        if self.seg.n_groups:
            group = {}
            for s in range(len(self.pattern)):
                li = self.cfg.first_dense_layers + s
                ls = self._layer_cache_spec(li, batch, max_len, window)
                group[f"slot{s}"] = jax.tree.map(
                    lambda sa: ((self.seg.n_groups,) + sa[0],
                                ("layers",) + sa[1]),
                    ls, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
                    and isinstance(x[0], tuple))
            spec["blocks"] = group
        for j, i in enumerate(self.seg.tail):
            spec[f"tail_{j}"] = self._layer_cache_spec(i, batch, max_len, window)
        return spec

    def init_cache(self, batch, max_len, window=None, dtype=jnp.bfloat16,
                   abstract=False):
        spec = self.cache_spec(batch, max_len, window)

        def mk(path_name, sa):
            shape, _ = sa
            dt = jnp.float32 if path_name == "h" else dtype
            if abstract:
                return jax.ShapeDtypeStruct(shape, dt)
            return jnp.zeros(shape, dt)

        def walk(node):
            return {k: (mk(k, v) if _is_sa(v) else walk(v))
                    for k, v in node.items()}

        def _is_sa(v):
            return (isinstance(v, tuple) and len(v) == 2
                    and isinstance(v[0], tuple))

        out = walk(spec)
        if jnp.dtype(dtype) in (jnp.dtype(jnp.float8_e4m3fn),
                                jnp.dtype(jnp.float8_e5m2)):
            # fp8 KV quantizes on write with a per-token-per-head scale;
            # the scale leaves live beside k/v so the same pytree carries
            # both (see attention.attn_apply)
            def add_scales(node):
                for v in node.values():
                    if isinstance(v, dict):
                        add_scales(v)
                if "k" in node and not isinstance(node["k"], dict):
                    for leaf in ("k", "v"):
                        sh = node[leaf].shape[:-1]
                        node[leaf + "_scale"] = (
                            jax.ShapeDtypeStruct(sh, jnp.float32) if abstract
                            else jnp.ones(sh, jnp.float32))
            add_scales(out)
        return out

    def cache_axes(self, batch, max_len, window=None):
        spec = self.cache_spec(batch, max_len, window)
        def _is_sa(v):
            return (isinstance(v, tuple) and len(v) == 2
                    and isinstance(v[0], tuple))
        def walk(node):
            return {k: (v[1] if _is_sa(v) else walk(v))
                    for k, v in node.items()}
        return walk(spec)

    # ------------------------------------------------------------ forward
    def _apply_layer(self, p, bt, x, positions, mode, cache, window,
                     triangular=True, block_table=None, dst_page=None,
                     layer=None):
        kw = {}
        if bt in ("attn", "mla"):
            kw["triangular"] = triangular
        if bt == "attn":
            kw["window"] = window or self.cfg.attn_window
            if block_table is not None:
                kw["block_table"] = block_table
            if dst_page is not None:
                kw["dst_page"] = dst_page
            if layer is not None:
                kw["layer"] = layer
        c_in = cache["mixer"] if cache is not None else None
        x, new_c = BLOCK_APPLY[bt](self.cfg, p["mixer"], x, positions,
                                   mode=mode, cache=c_in, **kw)
        aux = jnp.zeros((), jnp.float32)
        if "moe" in p:
            y, aux = moe.moe_apply(self.cfg, p["moe"], x)
            x = x + y
        elif "mlp" in p:
            x = x + swiglu_apply(p["mlp"], rms_norm(x, p["mlp"]["ln"]))
        return x, ({"mixer": new_c} if new_c is not None else None), aux

    def forward(self, params, *, tokens=None, embeddings=None, mode="full",
                cache=None, pos=None, window=None, remat=False,
                triangular=True, block_table=None, dst_page=None):
        """Returns (logits, new_cache, aux_loss).

        mode='full': tokens (B,S) and/or embeddings (B,P,d); positions 0..S-1.
        mode='decode': tokens (B,1); ``pos`` scalar absolute position; cache
        required (built by init_cache). Paged decode (cache leaves built by
        ``serving.kvpool``) additionally takes ``block_table`` (B, N) and
        allows ``pos`` to be a (B,) vector of per-sequence positions.
        mode='chunk': page-aligned prefill chunk runs against the paged
        pool — tokens (B, C*page_size) with one independent run per row,
        ``pos`` the (B,) absolute positions of each run's first token
        (scalar accepted for B == 1), ``block_table`` (B, N) covering
        every page each sequence occupies through its run, ``dst_page``
        (B, C) page ids the runs' K/V is scattered onto (the scratch page
        for prefix-shared chunks and padding). Attention-only patterns.

        Paged modes (a ``block_table`` is given) carry the stacked page
        pool through the layer scan and write each layer's pages in place:
        slicing it per layer as scan input and restacking it as output
        would hold the whole pool twice."""
        cfg = self.cfg
        emb = params["embed"]
        if embeddings is not None and tokens is not None:
            x = jnp.concatenate(
                [embeddings.astype(emb.dtype), emb[tokens]], axis=1)
        elif embeddings is not None:
            x = embeddings.astype(emb.dtype)
        else:
            x = emb[tokens]
        x = shard(x, "batch", None, None)
        B, S = x.shape[0], x.shape[1]

        if mode == "full":
            positions = jnp.arange(S, dtype=jnp.int32)
        elif mode == "chunk":
            # scalar pos -> (1,S); (B,) per-row starts -> (B,S)
            starts = jnp.asarray(pos, jnp.int32).reshape(-1)
            positions = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        else:
            positions = pos

        aux_total = jnp.zeros((), jnp.float32)
        new_cache = {}

        def run_single(name, idx, x, aux_total):
            c = cache.get(name) if cache is not None else None
            bt = _layer_block_type(cfg, idx)
            x, nc, aux = self._apply_layer(params[name], bt, x, positions,
                                           mode, c, window, triangular,
                                           block_table, dst_page)
            if nc is not None:
                new_cache[name] = nc
            return x, aux_total + aux

        for i in self.seg.head:
            x, aux_total = run_single(f"head_{i}", i, x, aux_total)

        if self.seg.n_groups:
            pat = self.pattern
            cb = cache.get("blocks") if cache is not None else None
            paged = block_table is not None and cb is not None

            def group_body(carry, xs):
                x, aux, pool = carry
                pslice, cslice, li = xs
                ncs = {}
                for s, bt in enumerate(pat):
                    c = pool if paged else cslice
                    c = c[f"slot{s}"] if c is not None else None
                    x, nc, a = self._apply_layer(
                        pslice[f"slot{s}"], bt, x, positions, mode, c, window,
                        triangular, block_table, dst_page,
                        layer=li if paged else None)
                    if nc is not None:
                        ncs[f"slot{s}"] = nc
                    aux = aux + a
                if paged:
                    return (x, aux, ncs), None
                return (x, aux, None), (ncs if ncs else None)

            body = jax.checkpoint(group_body) if remat else group_body
            (x, aux_total, pool), ys = jax.lax.scan(
                body, (x, aux_total, cb if paged else None),
                (params["blocks"], None if paged else cb,
                 jnp.arange(self.seg.n_groups, dtype=jnp.int32)))
            if paged:
                new_cache["blocks"] = pool
            elif ys is not None:
                new_cache["blocks"] = ys

        for j, i in enumerate(self.seg.tail):
            x, aux_total = run_single(f"tail_{j}", i, x, aux_total)

        x = rms_norm(x, params["final_norm"])
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, emb)
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
        logits = shard(logits, "batch", None, "vocab")
        return logits, (new_cache if new_cache else None), aux_total


def get_model(cfg) -> Model:
    return Model(cfg)
