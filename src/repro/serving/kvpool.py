"""Trust-tiered paged KV-cache pool (vLLM-style, privacy-aware).

The dense stacked slot cache (PR 1's ``ContinuousBatcher``) reserves
O(max_len) KV rows per slot for the slot's whole lifetime and can never
share state between requests. This module replaces that with a fixed-page
block pool:

* physical storage is ``num_pages`` pages of ``page_size`` tokens each, one
  (num_pages, page_size, Hkv, D) array per attention-layer cache leaf
  (page id indexes EVERY layer's array, so one block table serves the whole
  model — the standard vLLM layout);
* a free list + per-page refcounts give allocate/free at page granularity:
  sequences allocate pages lazily as they decode and release them at
  completion, so pool memory tracks *live tokens*, not slot capacity;
* pages are **copy-on-write**: a page with refcount > 1 is frozen; a writer
  must ``cow()`` it (copy to a fresh page) before appending, which is what
  makes prefix sharing safe;
* prefix sharing is **trust-tiered**: every page carries the MIST trust
  tier of the request that produced it, and the prefix index is keyed by
  ``(tier, chain_hash, fill)`` — a request can only attach to a cached
  prefix page produced at *exactly its own tier*.  Requests without a tier
  and pools whose island's TIDE has crashed share nothing (fail closed).

Page 0 is reserved as a scratch page: inactive decode slots point their
block tables at it so the fused decode step can write their dummy tokens
somewhere harmless.

The pool is deliberately split into host-side accounting (pure Python —
this is what the property tests drive) and device-side page storage (built
from ``model.cache_spec`` and mutated by three jitted ops: prompt-chunk
scatter, page copy, and the decode step itself via
``kernels.paged_attention``).
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.migration import PageRecord

SCRATCH_PAGE = 0


# --------------------------------------------------------------- trust tiers

def trust_tier_for_sensitivity(s_r: float) -> int:
    """Map a MIST sensitivity score to the three-tier trust hierarchy used
    to tag KV pages (mirrors the island tiers: 1 personal, 2 private edge,
    3 cloud). High-sensitivity state may only ever be shared with requests
    in the same high tier."""
    if s_r >= 0.8:
        return 1
    if s_r >= 0.5:
        return 2
    return 3


def prefix_chunk_hashes(token_ids, page_size: int):
    """Chain hashes over page-sized chunks of a prompt.

    Returns ``[(hash, fill), ...]`` — one entry per chunk, where ``hash``
    commits to every token from position 0 through the chunk's end and
    ``fill`` is the number of tokens in the chunk (== page_size except
    possibly for the last).  Chaining means equal hash => equal *entire
    prefix*, which is the invariant that makes page sharing sound.
    """
    out = []
    h = hashlib.sha256(b"kvpool-prefix")
    for start in range(0, len(token_ids), page_size):
        chunk = token_ids[start:start + page_size]
        h.update(np.asarray(chunk, np.int32).tobytes())
        h.update(len(chunk).to_bytes(4, "little"))
        out.append((h.hexdigest(), len(chunk)))
    return out


def resolve_chunk_page(pool: "PagePool", tier: Optional[int], chash: str,
                       fill: int):
    """Late-binding prefix resolution for one planned prefill chunk.

    Chunked admission plans chunks without dispatching them, so a chunk
    another request registered *after* this request was admitted (it was
    mid-prefill at admission time) is re-probed here, at dispatch time:
    attach to the registered page (skip the FLOPs) or take a fresh page.
    The attach path goes through ``lookup_prefix``, so every fail-closed
    rule — tier mismatch, untiered request, sharing disabled — applies
    identically; registration-after-write (the batcher registers a page
    only once its K/V is in the pool) guarantees any hit is readable.
    Returns ``(page_id_or_None, attached)``.
    """
    pid = pool.lookup_prefix(tier, chash, fill)
    if pid is not None:
        pool.incref(pid)
        return pid, True
    return pool.alloc(tier), False


# -------------------------------------------------------------- device ops

def _leaf_page_axis(leaf) -> int:
    """Pool leaves are (P, ps, Hkv, D) or, for scanned layer groups,
    (G, P, ps, Hkv, D)."""
    return 0 if leaf.ndim == 4 else 1


def _write_pages(pages, dense, page_ids, *, ps):
    """Scatter EVERY page-sized chunk of a (1, max_len, ...) dense prefill
    cache into the pool in one dispatch: chunk j lands on ``page_ids[j]``.
    Chunks the caller wants skipped (already-shared pages, positions past
    the prompt) map to the scratch page 0, whose content is never read —
    this keeps the call a single fixed-shape scatter per admission instead
    of one dispatch per page.

    The two pytrees are isomorphic but the pool renames leaves (k ->
    k_pages), so leaves are zipped positionally rather than tree-mapped.
    """
    def one(p, d):
        if p.ndim == 4:                      # (P, ps, Hkv, D) <- (1, S, ...)
            chunks = d[0].reshape(-1, ps, *d.shape[2:]).astype(p.dtype)
            return p.at[page_ids].set(chunks)
        # (G, P, ps, Hkv, D) <- (G, 1, S, ...)
        chunks = d[:, 0].reshape(d.shape[0], -1, ps,
                                 *d.shape[3:]).astype(p.dtype)
        return p.at[:, page_ids].set(chunks)
    p_leaves, p_def = jax.tree.flatten(pages)
    d_leaves = jax.tree.leaves(dense)
    assert len(p_leaves) == len(d_leaves)
    return jax.tree.unflatten(p_def, [one(p, d) for p, d
                                      in zip(p_leaves, d_leaves)])


def _copy_page(pages, src, dst):
    """dst page := src page, every leaf (the COW copy)."""
    def one(p):
        if p.ndim == 4:
            row = jax.lax.dynamic_index_in_dim(p, src, 0, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(p, row, dst, 0)
        row = jax.lax.dynamic_index_in_dim(p, src, 1, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(p, row, dst, 1)
    return jax.tree.map(one, pages)


def _set_page(pages, data, dst):
    """dst page := ``data`` (one exported page's leaves, positional —
    the migration-import write)."""
    p_leaves, p_def = jax.tree.flatten(pages)
    out = []
    for p, d in zip(p_leaves, data):
        d = jnp.asarray(d).astype(p.dtype)
        axis = 0 if p.ndim == 4 else 1
        out.append(jax.lax.dynamic_update_index_in_dim(p, d, dst, axis))
    return jax.tree.unflatten(p_def, out)


def _set_pages(pages, data, dst):
    """Batched ``_set_page``: scatter k exported pages in ONE dispatch.
    ``data`` leaves are stacked page-major — (k, ps, Hkv, D) for 4-d pool
    leaves, (k, G, ps, Hkv, D) for scanned layer groups."""
    p_leaves, p_def = jax.tree.flatten(pages)
    out = []
    for p, d in zip(p_leaves, data):
        d = jnp.asarray(d).astype(p.dtype)
        if p.ndim == 4:
            out.append(p.at[dst].set(d))
        else:
            out.append(p.at[:, dst].set(jnp.moveaxis(d, 0, 1)))
    return jax.tree.unflatten(p_def, out)


# ---------------------------------------------------------------- the pool

@dataclass
class _PageMeta:
    tier: Optional[int] = None
    key: Optional[tuple] = None     # (tier, hash, fill) while indexed


class PagePool:
    """Refcounted, trust-tier-tagged fixed-page KV pool.

    ``model=None`` builds an accounting-only pool (no device arrays) —
    that's what the allocation/free/sharing property tests exercise; the
    serving path passes the real model so ``pages`` holds per-layer
    (num_pages, page_size, Hkv, D) storage, committed to ``device`` (the
    device holding the owning batcher's weights; None = the default).
    """

    def __init__(self, model=None, max_len: int = 256, page_size: int = 16,
                 num_pages: int = 64, dtype=jnp.bfloat16, sharing: bool = True,
                 device=None):
        assert num_pages >= 2, "need at least scratch + 1 usable page"
        if max_len % page_size:
            # the prompt-chunk scatter slices the (1, max_len) dense prefill
            # cache in whole pages; a ragged tail slice would CLAMP its start
            # (lax.dynamic_slice semantics) and silently write shifted K/V
            raise ValueError(
                f"max_len ({max_len}) must be a multiple of page_size "
                f"({page_size})")
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_len = max_len
        self.sharing_enabled = sharing
        self.refcount = np.zeros(num_pages, np.int32)
        self.refcount[SCRATCH_PAGE] = 1          # never allocated, never freed
        self._free = list(range(num_pages - 1, 0, -1))   # pop() -> low ids first
        self._meta = {pid: _PageMeta() for pid in range(num_pages)}
        self._prefix_index: dict[tuple, int] = {}
        self.stats = {"allocs": 0, "frees": 0, "share_hits": 0,
                      "share_misses": 0, "cow_copies": 0, "blocked": 0,
                      "peak_in_use": 0, "exported_pages": 0,
                      "imported_pages": 0, "import_attach_hits": 0,
                      "import_tier_mismatch": 0, "import_refused": 0}
        # per-trust-tier counters backing the tier-scoped telemetry view:
        # a viewer must never need the raw per-island counters to know its
        # OWN tier's sharing behaviour, and the aggregated view must be
        # computable without walking page metadata on every report
        self.tier_stats: dict = {}
        # optional span-trace hook (set by the owning batcher's
        # ``attach_tracer``): called as hook(kind, **attrs) on page
        # alloc / COW / prefix-share hits. Pure observation — never
        # consulted for any allocation decision.
        self.trace_hook = None
        self.pages = None
        self._write_pages_fn = None
        self._copy_page_fn = None
        self._set_page_fn = None
        if model is not None:
            spec = model.cache_spec(1, max_len)
            self.pages = self._build_pages(spec, dtype, device)
            self._write_pages_fn = jax.jit(
                partial(_write_pages, ps=page_size), donate_argnums=(0,))
            self._copy_page_fn = jax.jit(_copy_page, donate_argnums=(0,))
            self._set_page_fn = jax.jit(_set_page, donate_argnums=(0,))
            self._set_pages_fn = jax.jit(_set_pages, donate_argnums=(0,))

    def _build_pages(self, spec, dtype, device):
        def _is_sa(v):
            return (isinstance(v, tuple) and len(v) == 2
                    and isinstance(v[0], tuple))

        def mk(name, sa):
            shape, _ = sa
            if name not in ("k", "v"):
                raise ValueError(
                    f"paged KV pool only supports attention caches, got "
                    f"cache leaf {name!r} (use the stacked batcher for "
                    f"ssm/rglru/mla patterns)")
            if len(shape) == 4:              # (1, S, Hkv, D)
                _, _, hkv, d = shape
                out = (self.num_pages, self.page_size, hkv, d)
            else:                            # (G, 1, S, Hkv, D)
                g, _, _, hkv, d = shape
                out = (g, self.num_pages, self.page_size, hkv, d)
            return jnp.zeros(out, dtype, device=device)

        def walk(node):
            out = {}
            for k, v in node.items():
                if _is_sa(v):
                    out[k + "_pages"] = mk(k, v)
                else:
                    out[k] = walk(v)
            return out

        return walk(spec)

    # ------------------------------------------------------------ accounting
    def _tstat(self, tier) -> dict:
        d = self.tier_stats.get(tier)
        if d is None:
            d = self.tier_stats[tier] = {"allocs": 0, "share_hits": 0,
                                         "share_misses": 0}
        return d

    def snapshot_share_counters(self):
        """Share-hit/miss counters (global + per tier), for callers that
        probe ``lookup_prefix`` speculatively — admission planning and
        migration import — and must roll the counters back so telemetry
        reflects only committed sharing decisions."""
        return (self.stats["share_hits"], self.stats["share_misses"],
                {t: (d["share_hits"], d["share_misses"])
                 for t, d in self.tier_stats.items()})

    def restore_share_counters(self, snap):
        hits, misses, tiers = snap
        self.stats["share_hits"] = hits
        self.stats["share_misses"] = misses
        for t, d in self.tier_stats.items():
            h, m = tiers.get(t, (0, 0))
            d["share_hits"], d["share_misses"] = h, m

    def note_admission_attach(self, tier, n: int):
        """Count ``n`` admission-time prefix attaches (chunked admission
        rolls back its planning probes and re-credits only the chunks it
        actually attached to)."""
        if n:
            self.stats["share_hits"] += n
            self._tstat(tier)["share_hits"] += n

    def in_use_by_tier(self) -> dict:
        """Live page counts grouped by the trust tier tag on each page."""
        out: dict = {}
        for pid in range(1, self.num_pages):
            if self.refcount[pid] > 0:
                t = self._meta[pid].tier
                out[t] = out.get(t, 0) + 1
        return out

    def tier_telemetry(self) -> dict:
        """Per-trust-tier slice of the pool counters. This is the ONLY
        pool view that may cross a trust boundary: the lighthouse's
        tier-scoped telemetry aggregates these per-tier rows over the mesh
        so a tenant never sees another tier's (or island's) raw counters."""
        in_use = self.in_use_by_tier()
        out = {}
        for t in set(in_use) | set(self.tier_stats):
            s = self.tier_stats.get(t, {})
            out[t] = {"pages_in_use": in_use.get(t, 0),
                      "allocs": s.get("allocs", 0),
                      "share_hits": s.get("share_hits", 0),
                      "share_misses": s.get("share_misses", 0)}
        return out

    def in_use(self) -> int:
        """Allocated pages (excluding the reserved scratch page)."""
        return self.num_pages - 1 - len(self._free)

    def free_count(self) -> int:
        return len(self._free)

    def occupancy(self) -> float:
        return self.in_use() / max(self.num_pages - 1, 1)

    def alloc(self, tier: Optional[int] = None) -> Optional[int]:
        """Take a free page (tagged with the requester's trust tier).
        Returns None when the pool is exhausted — callers treat that as
        admission backpressure, not an error."""
        if not self._free:
            self.stats["blocked"] += 1
            return None
        pid = self._free.pop()
        assert self.refcount[pid] == 0
        self.refcount[pid] = 1
        self._meta[pid] = _PageMeta(tier=tier)
        self.stats["allocs"] += 1
        self._tstat(tier)["allocs"] += 1
        self.stats["peak_in_use"] = max(self.stats["peak_in_use"],
                                        self.in_use())
        if self.trace_hook is not None:
            self.trace_hook("page_alloc", page=pid, tier=tier,
                            in_use=self.in_use())
        return pid

    def incref(self, pid: int):
        assert pid != SCRATCH_PAGE and self.refcount[pid] > 0
        self.refcount[pid] += 1

    def decref(self, pid: int):
        assert pid != SCRATCH_PAGE, "scratch page is never freed"
        assert self.refcount[pid] > 0, f"double free of page {pid}"
        self.refcount[pid] -= 1
        if self.refcount[pid] == 0:
            meta = self._meta[pid]
            if meta.key is not None:
                self._prefix_index.pop(meta.key, None)
            self._meta[pid] = _PageMeta()
            self._free.append(pid)
            self.stats["frees"] += 1

    # ---------------------------------------------------------- prefix index
    def lookup_prefix(self, tier: Optional[int], chash: str,
                      fill: int) -> Optional[int]:
        """Find a live page holding this exact prefix chunk at this exact
        trust tier. Tier None (unknown sensitivity) and disabled sharing
        both fail closed: nothing is ever returned."""
        if not self.sharing_enabled or tier is None:
            return None
        pid = self._prefix_index.get((tier, chash, fill))
        if pid is None:
            self.stats["share_misses"] += 1
            self._tstat(tier)["share_misses"] += 1
            return None
        assert self._meta[pid].tier == tier      # impossible by construction
        self.stats["share_hits"] += 1
        self._tstat(tier)["share_hits"] += 1
        if self.trace_hook is not None:
            self.trace_hook("page_share", page=pid, tier=tier)
        return pid

    def register_prefix(self, pid: int, tier: Optional[int], chash: str,
                        fill: int):
        """Publish page ``pid`` as the canonical holder of a full
        prompt-prefix chunk. Contract: a registered page must be
        READABLE by the time any other request's dispatch gathers it —
        the unfused batcher registers strictly after the chunk write,
        the fused batcher registers at PLAN time, which is equivalent
        because the page is written by the same tick's single fused
        dispatch and a same-dispatch attacher gathers after every
        layer's scatter."""
        if not self.sharing_enabled or tier is None:
            return
        key = (tier, chash, fill)
        if key in self._prefix_index:
            return                               # first writer wins
        self._prefix_index[key] = pid
        self._meta[pid].key = key

    def disable_sharing(self):
        """Fail closed (crashed TIDE, unattested island): stop both lookups
        and registrations. Existing shared pages stay refcounted/safe."""
        self.sharing_enabled = False

    # ------------------------------------------------------------------ COW
    def cow(self, pid: int, tier: Optional[int] = None) -> Optional[int]:
        """Copy-on-write: take a private copy of ``pid`` for a writer.
        Decrefs the original, returns the new page id (None if the pool is
        exhausted; the caller must then stall the writer)."""
        new = self.alloc(tier if tier is not None else self._meta[pid].tier)
        if new is None:
            return None
        if self.pages is not None:
            self.pages = self._copy_page_fn(self.pages, jnp.int32(pid),
                                            jnp.int32(new))
        self.decref(pid)
        self.stats["cow_copies"] += 1
        if self.trace_hook is not None:
            self.trace_hook("page_cow", page=pid, new_page=new)
        return new

    # ----------------------------------------------------------- device I/O
    def write_prompt_pages(self, dense_cache, page_ids):
        """Scatter a whole admission's prompt chunks from the (1, max_len)
        dense prefill cache into the pool in ONE jitted dispatch (donated
        pool buffers). ``page_ids`` must cover every max_len/page_size
        chunk; entries set to the scratch page (0) are skip markers for
        already-shared pages and positions past the prompt."""
        ids = np.zeros(self.max_len // self.page_size, np.int32)
        ids[:len(page_ids)] = page_ids
        self.pages = self._write_pages_fn(self.pages, dense_cache,
                                          jnp.asarray(ids))

    def read_page(self, pid: int):
        """One page's K/V content as a positional list of host arrays
        (None on accounting-only pools) — the migration-export read."""
        if self.pages is None:
            return None
        return [np.asarray(p[pid] if p.ndim == 4 else p[:, pid])
                for p in jax.tree.leaves(self.pages)]

    def read_pages(self, pids):
        """Batched ``read_page``: ONE gather + host transfer per cache
        leaf for the whole list (the per-record views share the stacked
        buffers). Returns one leaf list per page id."""
        if not pids:
            return []
        if self.pages is None:
            return [None] * len(pids)
        idx = jnp.asarray(pids, jnp.int32)
        stacked = [np.asarray(p[idx] if p.ndim == 4
                              else jnp.moveaxis(p[:, idx], 1, 0))
                   for p in jax.tree.leaves(self.pages)]
        return [[leaf[n] for leaf in stacked] for n in range(len(pids))]

    def write_page(self, pid: int, data):
        """Overwrite one page from an exported record's leaf list."""
        assert pid != SCRATCH_PAGE
        self.pages = self._set_page_fn(self.pages, tuple(data),
                                       jnp.int32(pid))

    def write_pages(self, pids, datas):
        """Batched ``write_page``: scatter a whole import in ONE jitted
        dispatch (``datas`` is one exported leaf list per page)."""
        if not pids:
            return
        assert SCRATCH_PAGE not in pids
        stacked = tuple(np.stack([d[i] for d in datas])
                        for i in range(len(datas[0])))
        self.pages = self._set_pages_fn(self.pages, stacked,
                                        jnp.asarray(pids, jnp.int32))

    # ------------------------------------------------------------ telemetry
    def telemetry(self) -> dict:
        return {
            "num_pages": self.num_pages - 1,
            "in_use": self.in_use(),
            "occupancy": round(self.occupancy(), 4),
            "peak_in_use": self.stats["peak_in_use"],
            "share_hits": self.stats["share_hits"],
            "share_misses": self.stats["share_misses"],
            "share_hit_rate": round(
                self.stats["share_hits"]
                / max(self.stats["share_hits"] + self.stats["share_misses"],
                      1), 4),
            "cow_copies": self.stats["cow_copies"],
            "blocked": self.stats["blocked"],
            "sharing_enabled": self.sharing_enabled,
            "exported_pages": self.stats["exported_pages"],
            "imported_pages": self.stats["imported_pages"],
            "import_attach_hits": self.stats["import_attach_hits"],
            "import_tier_mismatch": self.stats["import_tier_mismatch"],
        }

    # ------------------------------------------------------------ invariants
    def audit(self):
        """Full invariant sweep (the property tests' oracle):

        * the free list holds each page at most once, every listed page has
          refcount 0, and no freed page keeps metadata or an index entry;
        * refcount conservation: live pages == lifetime allocs - frees, so
          no export/import/COW/free interleaving can leak a page or free
          one twice without tripping here;
        * the scratch page is permanently pinned (refcount exactly 1);
        * the prefix index and page metadata agree both ways, and every
          index entry's tier matches its page's tier tag — a cross-tier
          entry (a migrated page landing in a foreign tier's index) is
          structurally impossible and asserted anyway.
        """
        assert len(set(self._free)) == len(self._free), "free list dup"
        for pid in self._free:
            assert self.refcount[pid] == 0, f"free page {pid} has refs"
            meta = self._meta[pid]
            assert meta.tier is None and meta.key is None, \
                f"free page {pid} kept metadata"
        live = self.in_use()
        assert live == sum(1 for p in range(1, self.num_pages)
                           if self.refcount[p] > 0)
        assert live == self.stats["allocs"] - self.stats["frees"], \
            "refcount conservation broken (leak or double free)"
        assert self.refcount[SCRATCH_PAGE] == 1, "scratch page unpinned"
        for key, pid in self._prefix_index.items():
            assert self.refcount[pid] > 0, "index points at freed page"
            assert self._meta[pid].key == key, "index/meta disagree"
            assert self._meta[pid].tier == key[0], "cross-tier index entry"
        for pid, meta in self._meta.items():
            if meta.key is not None:
                assert self._prefix_index.get(meta.key) == pid, \
                    f"page {pid} claims an index key it doesn't hold"
        return True

    def check(self):
        """Back-compat alias for :meth:`audit`."""
        return self.audit()


# ----------------------------------------------------- migration (export)

def export_request(pool: PagePool, page_ids, kv_tokens: int,
                   detach: bool = True):
    """Serialize one request's live pages for cross-island migration.

    Returns one ``PageRecord`` per page, in block-table order: the page's
    trust tier, its prefix-index key when it holds a registered full
    prompt-prefix chunk (so the destination can re-attach by chain hash
    instead of copying bytes), its fill level within the request's
    ``kv_tokens`` context, and the page content (None on accounting-only
    pools). ``detach=True`` (the default) decrefs every page afterwards —
    the request has LEFT this pool; shared pages survive under their other
    referents, private pages free immediately.
    """
    ps = pool.page_size
    datas = pool.read_pages(list(page_ids))
    records = []
    for n, pid in enumerate(page_ids):
        meta = pool._meta[pid]
        fill = max(0, min(ps, kv_tokens - n * ps))
        records.append(PageRecord(tier=meta.tier, key=meta.key, fill=fill,
                                  data=datas[n]))
    if detach:
        for pid in page_ids:
            pool.decref(pid)
    pool.stats["exported_pages"] += len(records)
    return records


def import_request(pool: PagePool, records, tier: Optional[int]):
    """Materialize exported pages in this pool, all-or-nothing.

    Per record: a prefix-keyed page first probes the destination's OWN
    prefix index through the tier-keyed ``lookup_prefix`` — a hit means
    this pool already holds identical K/V at the request's exact tier, so
    the page re-attaches (increfed, zero bytes shipped). Everything else
    deep-copies into a freshly allocated page tagged with the record's
    tier and, when keyed, registers in the index for future sharers.

    Fail-closed rules, enforced here so no caller can launder trust:
    untiered requests never import (``tier is None`` -> recompute path);
    a record whose tier differs from the request's refuses the WHOLE
    import; a pool that stores real K/V refuses records without data.
    Pool exhaustion mid-import rolls everything back. Returns
    ``(page_ids, copied, attach_hits)`` or None (caller must fall back to
    recompute-from-tokens).
    """
    if tier is None:
        pool.stats["import_refused"] += 1
        return None
    for rec in records:
        if rec.tier != tier:
            pool.stats["import_tier_mismatch"] += 1
            pool.stats["import_refused"] += 1
            return None
    counters0 = pool.snapshot_share_counters()
    got: list[tuple[int, bool]] = []
    copies: list[tuple[int, PageRecord]] = []

    def rollback():
        for pid, _ in got:
            pool.decref(pid)
        pool.restore_share_counters(counters0)
        pool.stats["import_refused"] += 1
        return None

    for rec in records:
        # re-attach only when the page holds EXACTLY the registered chunk:
        # a tail page the source kept appending decode tokens to carries
        # content past the key's fill that the hash does not commit to, so
        # a destination index hit only guarantees the first key-fill
        # tokens — attaching would graft someone else's (or stale) KV at
        # the positions beyond. Mutated partials always deep-copy.
        if rec.key is not None and rec.fill == rec.key[2]:
            hit = pool.lookup_prefix(*rec.key)
            if hit is not None:
                pool.incref(hit)
                got.append((hit, True))
                continue
        if pool.pages is not None and rec.data is None:
            return rollback()        # no bytes to materialize
        pid = pool.alloc(rec.tier)
        if pid is None:
            return rollback()        # exhausted: caller recomputes
        got.append((pid, False))
        copies.append((pid, rec))
    # the whole import is decided: materialize every copied page in ONE
    # fused scatter, registering strictly AFTER the write (hits must
    # always be readable)
    if pool.pages is not None and copies:
        pool.write_pages([pid for pid, _ in copies],
                         [rec.data for _, rec in copies])
    for pid, rec in copies:
        if rec.key is not None:
            pool.register_prefix(pid, *rec.key)
    attach_hits = sum(1 for _, a in got if a)
    copied = len(copies)
    pool.stats["imported_pages"] += copied
    pool.stats["import_attach_hits"] += attach_hits
    return [pid for pid, _ in got], copied, attach_hits
