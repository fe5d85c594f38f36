"""Mixture-of-Experts block.

Two execution paths sharing one parameter table:

* ``dense`` — every expert applied to every token, combined with the routing
  weights. O(T*E*d*f) FLOPs: only for smoke-scale configs / as the numerical
  oracle.
* ``expert_parallel`` — shard_map over the mesh: tokens sharded on the batch
  axes, experts sharded on the model axis. Each device dispatches its local
  tokens to its local experts through a capacity-bounded scatter (sort-rank),
  runs the expert FFNs as one batched matmul, gathers back, and psums expert
  contributions over the model axis. This is the production path the dry-run
  lowers (the psum/all-reduce shows up in the §Roofline collective term).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import PSpec, rms_norm, swiglu_table, swiglu_apply
from repro.sharding import current_mesh, shard


def moe_table(cfg):
    E, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    t = {
        "ln": PSpec((d,), (None,), "zeros"),
        "router": PSpec((d, E), (None, None), scale=d ** -0.5),
        "we_gate": PSpec((E, d, f), ("experts", None, None)),
        "we_up": PSpec((E, d, f), ("experts", None, None)),
        "we_down": PSpec((E, f, d), ("experts", None, None)),
    }
    if cfg.num_shared_experts:
        t["shared"] = swiglu_table(d, cfg.num_shared_experts * f)
    return t


def _route(logits, k):
    """fp32 logits (T,E) -> (weights (T,k), idx (T,k), probs (T,E))."""
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)  # renormalize top-k
    return w, idx, probs


def _aux_loss(probs, idx, E):
    """Switch-style load-balance loss: E * sum_e f_e * p_e."""
    counts = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(counts.sum(), 1.0)
    p = probs.mean(axis=0)
    return E * jnp.sum(f * p)


def _expert_ffn(weg, weu, wed, buf):
    """buf (E,C,d) -> (E,C,d)."""
    g = jnp.einsum("ecd,edf->ecf", buf, weg)
    u = jnp.einsum("ecd,edf->ecf", buf, weu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wed)


def _moe_dense(p, x2, k, E):
    logits = (x2 @ p["router"]).astype(jnp.float32)
    w, idx, probs = _route(logits, k)
    outs = _expert_ffn(p["we_gate"], p["we_up"], p["we_down"],
                       jnp.broadcast_to(x2[None], (E,) + x2.shape))
    # outs: (E, T, d); combine top-k
    sel = outs[idx, jnp.arange(x2.shape[0])[:, None]]  # (T, k, d)
    y = jnp.einsum("tk,tkd->td", w.astype(sel.dtype), sel)
    return y, _aux_loss(probs, idx, E)


def _rank_within_expert(eid_flat):
    """eid_flat (N,) int32 -> rank of each entry among equal expert ids."""
    n = eid_flat.shape[0]
    order = jnp.argsort(eid_flat)
    sorted_eid = eid_flat[order]
    starts = jnp.searchsorted(sorted_eid, sorted_eid, side="left")
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - starts.astype(jnp.int32)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    return rank


def _moe_local(p_router, weg, weu, wed, x2, *, k, E, E_loc, C, model_axis,
               batch_axes=()):
    """Per-device body under shard_map. x2 (T_loc, d) replicated over model;
    expert weights are the local slices (E_loc, ...)."""
    T, d = x2.shape
    m = jax.lax.axis_index(model_axis)
    logits = (x2 @ p_router).astype(jnp.float32)
    w, idx, probs = _route(logits, k)  # (T,k)
    rank = _rank_within_expert(idx.reshape(-1)).reshape(T, k)
    lid = idx - m * E_loc
    local = (idx >= m * E_loc) & (idx < (m + 1) * E_loc) & (rank < C)
    # route to a dropped slot when not local / over capacity
    lid_s = jnp.where(local, lid, E_loc)  # OOB -> dropped by scatter mode
    rank_s = jnp.where(local, rank, C)

    buf = jnp.zeros((E_loc, C, d), x2.dtype)
    for ki in range(k):
        buf = buf.at[lid_s[:, ki], rank_s[:, ki]].add(
            x2, mode="drop")
    out = _expert_ffn(weg, weu, wed, buf)  # (E_loc, C, d)
    y = jnp.zeros((T, d), jnp.float32)
    for ki in range(k):
        gathered = out.at[lid_s[:, ki], rank_s[:, ki]].get(
            mode="fill", fill_value=0)
        y = y + w[:, ki:ki + 1] * gathered.astype(jnp.float32)
    y = jax.lax.psum(y, model_axis)
    # load-balance loss over GLOBAL routing statistics (matches the dense
    # oracle): aggregate counts/probs across batch shards first
    counts = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    p_mean = probs.mean(axis=0)
    if batch_axes:
        counts = jax.lax.psum(counts, batch_axes)
        p_mean = jax.lax.pmean(p_mean, batch_axes)
    f = counts / jnp.maximum(counts.sum(), 1.0)
    aux = E * jnp.sum(f * p_mean)
    return y.astype(x2.dtype), aux


def moe_apply(cfg, p, x):
    """x (B,S,d) -> (y (B,S,d) [residual NOT added], aux scalar)."""
    B, S, d = x.shape
    h = rms_norm(x, p["ln"])
    x2 = h.reshape(B * S, d)
    mesh = current_mesh()
    k, E = cfg.top_k, cfg.num_experts

    if mesh is None or "model" not in mesh.shape:
        y, aux = _moe_dense(p, x2, k, E)
    else:
        model_size = mesh.shape["model"]
        assert E % model_size == 0
        E_loc = E // model_size
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        bsz = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
        if batch_axes and (B * S) % bsz != 0:
            # too few tokens to shard (e.g. long_500k decode, B=1):
            # replicate tokens, keep experts sharded.
            batch_axes = ()
            bsz = 1
        T_loc = (B * S) // bsz
        C = max(4, int(math.ceil(T_loc * k / E * cfg.capacity_factor)))
        x_spec = P(batch_axes if batch_axes else None, None)
        fn = partial(_moe_local, k=k, E=E, E_loc=E_loc, C=C,
                     model_axis="model", batch_axes=batch_axes)
        y, aux = jax.shard_map(
            fn, mesh=mesh, check_vma=False,
            in_specs=(P(None, None), P("model", None, None),
                      P("model", None, None), P("model", None, None),
                      x_spec),
            out_specs=(x_spec, P()),
        )(p["router"], p["we_gate"], p["we_up"], p["we_down"], x2)

    y = y.reshape(B, S, d)
    if cfg.num_shared_experts:
        y = y + swiglu_apply(p["shared"], h)
    return y, aux
