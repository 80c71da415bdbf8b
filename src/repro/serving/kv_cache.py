"""Serving cache managers.

Two layouts (DESIGN.md §2, §10 — hardware adaptation of vLLM's
PagedAttention):

* ``SlotCache`` — the model's native slot-based contiguous cache (fixed
  max_len per decode slot). Slot allocation/free is O(1); the jitted decode
  step is shape-stable. This is what JetStream-style TPU serving does
  instead of paging, and it remains the engine default.

* ``PagedCache`` — device-resident block-table KV pool: fixed-size physical
  pages shared across sequences, a ``(max_seqs, max_pages)`` int32 device
  block table consumed directly by the Pallas paged-attention decode kernel
  (``kernels/paged_attention.py``), refcounted free lists with
  copy-on-write on shared-page writes, and a hashed-prefix cache that
  reuses full pages across requests with identical prompt prefixes.

Physical page 0 is the **null page**: never allocated, permanently
refcounted, the target of block-table padding and of dead decode rows'
writes.  ``num_pages`` counts *allocatable* pages, so pool arrays hold
``num_pages + 1`` physical pages.

Both layouts take a ``kv_quant`` (``serving/kv_quant.py::KVQuantConfig``):
int8 payloads with parallel symmetric-scale pools, quantize-on-write /
dequantize-on-read fused into every data-path method (DESIGN.md §12).
``PagedCache`` supports per-token *and* per-page scale granularity; scale
pools ride along with their pages through copy-on-write and prefix sharing.

Overload resilience (DESIGN.md §14): ``offload(seq_id)`` checkpoints a
sequence's private pages to host memory and releases everything it holds
(shared prefix pages are *released, not copied* — their payload stays live
on device under the donor's refcount); ``restore(seq_id)`` re-allocates
through the normal admission path (prefix-cache hits included) and scatters
the host snapshot back.  Payload movement is pluggable (``gather``/
``scatter`` callables) because the engine keeps page payloads in its model
cache tree (``alloc_pools=False``); with ``alloc_pools=True`` the cache
moves its own pools.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import kv_quant as KQ

# Default dtype of a standalone SlotCache / PagedCache.  The engine passes
# its own: the model's dtype unless EngineConfig.cache_dtype says otherwise.
DEFAULT_CACHE_DTYPE = jnp.float32

NULL_PAGE = 0
# Pools are head-major, (layers, Hkv, pages, page_size, D) — scale pools
# (layers, Hkv, pages[, page_size]) — so one kv head's page is a
# (page_size, D) tile for the paged kernels; physical pages index this axis.
PAGE_AXIS = 2


def prefix_hash_seed(quant_tag: tuple, page_size: int) -> int:
    """Deterministic seed for the hashed-prefix chain, derived from the KV
    quant mode + page size via sha256 — NOT Python's ``hash()``, whose
    string hashing is randomized per process (PYTHONHASHSEED).  The rest of
    the chain (``hash((int_key, int_tuple))``) only ever hashes integers,
    which Python hashes deterministically, so a deterministic seed makes
    the whole chain stable across processes — the property that lets a
    persisted prefix index (DESIGN.md §16) be reloaded by a fresh engine."""
    blob = repr(("kv_prefix_seed_v1", page_size) + tuple(quant_tag))
    digest = hashlib.sha256(blob.encode("utf-8")).digest()
    return int.from_bytes(digest[:8], "big", signed=True)


class SlotCache:
    """Fixed-slot cache wrapper around the model's init_cache tree."""

    def __init__(self, model, batch_slots: int, max_len: int,
                 dtype=DEFAULT_CACHE_DTYPE, kv_quant=None):
        self.model = model
        self.batch_slots = batch_slots
        self.max_len = max_len
        self.kv_quant = kv_quant
        quantized = kv_quant is not None and kv_quant.quantized
        self.dtype = jnp.dtype(jnp.int8) if quantized else jnp.dtype(dtype)
        self.cache = model.init_cache(batch_slots, max_len, dtype=dtype,
                                      kv_quant=kv_quant)
        self.seq_lens = jnp.zeros((batch_slots,), jnp.int32)
        self._free = list(range(batch_slots))[::-1]
        self._live: set[int] = set()

    def alloc(self) -> int | None:
        if not self._free:
            return None
        slot = self._free.pop()
        self._live.add(slot)
        return slot

    def free(self, slot: int):
        self._live.discard(slot)
        self._free.append(slot)
        # zero this slot's length so masks exclude stale entries
        self.seq_lens = self.seq_lens.at[slot].set(0)

    @property
    def num_free(self) -> int:
        return len(self._free)


@dataclasses.dataclass
class OffloadedSeq:
    """Host-memory checkpoint of a preempted sequence (DESIGN.md §14).

    ``payload`` is a host copy of the sequence's *private* pages (logical
    pages ``[shared_pages, pages_needed(length))`` — ``PAGE_AXIS`` in every
    leaf, scale pools riding along).  The leading ``shared_pages`` full
    prefix pages were shared (refcount > 1) at offload time and were
    released without copying: their payload stays live on device under the
    donor's refcount, and restore re-finds them through the hashed-prefix
    cache — or recomputes them if the donor has since evicted."""
    seq_id: int
    length: int                 # context tokens the snapshot covers
    shared_pages: int           # leading prefix pages released, not copied
    payload: Any                # host pytree, PAGE_AXIS (None when empty)
    n_payload_pages: int
    nbytes: int                 # host bytes held by ``payload``


@dataclasses.dataclass
class RestoredSeq:
    """What ``restore`` did, for the engine's bookkeeping: prefix pages
    re-shared from the live cache, where the host snapshot started, and the
    pages scattered back.  Logical pages ``[hit_pages, snap_start_page)``
    (non-empty only when the donor evicted while this sequence was
    offloaded) hold no data — the caller must recompute that token span."""
    hit_pages: int
    snap_start_page: int
    length: int
    restored_pages: int


@dataclasses.dataclass
class PagedCache:
    """Block-table KV pool with a device-resident block table.

    k_pages/v_pages: (n_layers, Hkv, num_pages + 1, page_size, D) pools.
    block_tables   : (max_seqs, max_pages) int32 device array; row ``r`` maps
                     sequence-in-row-r logical page ``i`` to a physical page.
    Host bookkeeping (free list, refcounts, per-seq tables, prefix hashes)
    stays in plain Python/numpy; only page payloads and the block table are
    device arrays.
    """
    num_pages: int
    page_size: int
    n_layers: int
    kv_heads: int
    head_dim: int
    dtype: object = None            # None -> DEFAULT_CACHE_DTYPE
    max_seqs: int = 0               # 0 -> num_pages (every seq needs >=1 page)
    max_pages: int = 0              # block-table width; 0 -> num_pages
                                    # (the engine sizes both from its slots
                                    # and max_len / page_size)
    alloc_pools: bool = True        # False: bookkeeping only — the engine
                                    # stores page payloads in the model cache
                                    # tree (init_paged_cache), not here
    kv_quant: object = None         # KVQuantConfig: int8 pools + scale pools

    def __post_init__(self):
        # compute_dtype: what gather_kv returns; dtype: what the pools store
        self.compute_dtype = jnp.dtype(self.dtype if self.dtype is not None
                                       else DEFAULT_CACHE_DTYPE)
        quantized = self.kv_quant is not None and self.kv_quant.quantized
        self.dtype = jnp.dtype(jnp.int8) if quantized else self.compute_dtype
        self.max_seqs = self.max_seqs or self.num_pages
        self.max_pages = self.max_pages or self.num_pages
        shape = (self.n_layers, self.kv_heads, self.num_pages + 1,
                 self.page_size, self.head_dim)
        self.k_scales = self.v_scales = None
        if self.alloc_pools:
            self.k_pages = jnp.zeros(shape, self.dtype)
            self.v_pages = jnp.zeros(shape, self.dtype)
            if quantized:
                sshape = (self.n_layers,) + KQ.paged_scale_shape(
                    self.num_pages, self.page_size, self.kv_heads,
                    self.kv_quant.granularity)
                sdt = self.kv_quant.scale_jnp_dtype
                self.k_scales = jnp.zeros(sshape, sdt)
                self.v_scales = jnp.zeros(sshape, sdt)
        else:
            self.k_pages = self.v_pages = None
        self.seq_lens = jnp.zeros((self.max_seqs,), jnp.int32)
        # pop() order 1, 2, 3, ...; page 0 is the never-allocated null page
        self.free_list = list(range(self.num_pages, 0, -1))
        self.tables: dict[int, list[int]] = {}
        self.lengths: dict[int, int] = {}
        self.refcount = np.zeros(self.num_pages + 1, np.int32)
        self.refcount[NULL_PAGE] = np.iinfo(np.int32).max // 2   # pinned
        self.block_tables = jnp.zeros((self.max_seqs, self.max_pages),
                                      jnp.int32)
        self.rows: dict[int, int] = {}
        self._free_rows = list(range(self.max_seqs))[::-1]
        # hashed-prefix cache: chain-hash of page-aligned token prefixes.
        # The chain is seeded with the KV quant mode (ISSUE 6 satellite /
        # ROADMAP carry-over): pages written under one quant config can
        # never be served to a lookup under another — int8 payloads+scales
        # and bf16 payloads for the same tokens are different bytes, so
        # their keys must differ once prefix indexes outlive one cache
        # instance (persisted prefix caches, engine restarts).
        quant_tag = ((self.kv_quant.dtype, self.kv_quant.granularity)
                     if quantized else ("fp", str(self.compute_dtype)))
        self._hash_seed = prefix_hash_seed(quant_tag, self.page_size)
        self._prefix_index: dict[int, int] = {}      # hash key -> page id
        self._page_key: dict[int, int] = {}          # page id -> hash key
        self.prefix_hits: dict[int, int] = {}        # seq_id -> pages reused
        # preempted sequences' host-memory page checkpoints (DESIGN.md §14)
        self.offloaded: dict[int, OffloadedSeq] = {}

    # ------------------------------------------------------------ bookkeeping
    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def can_alloc(self, n_tokens: int) -> bool:
        return len(self.free_list) >= self.pages_needed(n_tokens)

    @property
    def utilization(self) -> float:
        return 1.0 - len(self.free_list) / self.num_pages

    def occupancy(self) -> dict:
        """Point-in-time pool occupancy for the observability layer
        (DESIGN.md §15): page-pool gauges and step-span annotations read
        this one snapshot instead of poking at internals."""
        return {
            "num_pages": self.num_pages,
            "free_pages": len(self.free_list),
            "utilization": self.utilization,
            "live_seqs": len(self.tables),
            "offloaded_seqs": len(self.offloaded),
            "offloaded_bytes": self.offloaded_bytes,
        }

    def row_of(self, seq_id: int) -> int:
        return self.rows[seq_id]

    def _sync_row(self, seq_id: int):
        """Push one sequence's host table into the device block table."""
        row = self.rows[seq_id]
        arr = np.full((self.max_pages,), NULL_PAGE, np.int32)
        table = self.tables[seq_id]
        arr[:len(table)] = table
        self.block_tables = self.block_tables.at[row].set(jnp.asarray(arr))

    def _prefix_keys(self, tokens) -> list[int]:
        """Chain hashes of each full-page-aligned prefix of ``tokens``,
        seeded with the KV quant mode so distinct quant configs can never
        collide on the same token prefix."""
        keys, key = [], self._hash_seed
        for i in range(len(tokens) // self.page_size):
            page = tuple(tokens[i * self.page_size:(i + 1) * self.page_size])
            key = hash((key, page))
            keys.append(key)
        return keys

    def alloc_seq(self, seq_id: int, n_tokens: int,
                  share_from: int | None = None,
                  tokens=None, reserve: int = 0) -> bool:
        """Allocate pages (and a block-table row) for a sequence.

        Prefix reuse, in priority order: ``share_from`` (explicit donor —
        full pages of the donor's table are refcounted in), else ``tokens``
        (the prompt ids) consults the hashed-prefix cache.  ``reserve``
        tokens of extra page capacity are allocated up front (the engine
        reserves the decode budget at admission so generation can never hit
        pool exhaustion mid-flight).  Returns False — with no state change —
        when pages or rows are unavailable.
        """
        if seq_id in self.tables:
            raise ValueError(f"seq {seq_id} already allocated")
        pages: list[int] = []
        shared = 0
        if share_from is not None and share_from in self.tables:
            src = self.tables[share_from]
            shared = min(len(src), n_tokens // self.page_size)
            pages = src[:shared]
        elif tokens is not None:
            keys = self._prefix_keys(tokens)[:self._max_shared_pages(n_tokens)]
            for key in keys:
                page = self._prefix_index.get(key)
                if page is None or self.refcount[page] <= 0:
                    break
                pages.append(page)
            shared = len(pages)
        need = self.pages_needed(n_tokens + reserve) - shared
        if (need > len(self.free_list) or not self._free_rows
                or self.pages_needed(n_tokens + reserve) > self.max_pages):
            return False
        pages = list(pages)               # never alias a donor's table
        for p in pages:
            self.refcount[p] += 1
        for _ in range(need):
            p = self.free_list.pop()
            self.refcount[p] += 1
            pages.append(p)
        self.tables[seq_id] = pages
        self.lengths[seq_id] = n_tokens
        self.rows[seq_id] = self._free_rows.pop()
        if tokens is not None and share_from is None:
            self.prefix_hits[seq_id] = shared
        self._sync_row(seq_id)
        return True

    def _max_shared_pages(self, n_tokens: int) -> int:
        """Prefix-cache hits are capped below full-prompt coverage: at least
        one suffix token must remain, or prefill would run over zero real
        tokens and the first sampled token would come from padding logits
        (ISSUE 5).  ``Engine._admit_paged`` guards the same invariant with a
        page backoff in case a future admission path bypasses this cap."""
        return (n_tokens - 1) // self.page_size

    def release_prefix(self, seq_id: int, keep: int) -> int:
        """Drop prefix sharing beyond the first ``keep`` pages of ``seq_id``:
        every later page of its table that is still shared (refcount > 1) is
        swapped for a fresh private page, so the caller can re-prefill the
        dropped span without scribbling on a donor's live page.  The old
        payload is never copied (unlike COW) — the caller rewrites the whole
        dropped span — which is what makes this safe with
        ``alloc_pools=False``, where the payloads live in the engine's model
        cache tree.  Returns the number of pages swapped; raises when the
        free list cannot supply a replacement."""
        table = self.tables[seq_id]
        swapped = 0
        try:
            for li in range(keep, len(table)):
                p = table[li]
                if self.refcount[p] <= 1:
                    continue               # already private: rewriting is safe
                if not self.free_list:
                    raise RuntimeError(
                        "page pool exhausted while privatizing prefix pages "
                        f"of seq {seq_id} (backoff from page {keep})")
                q = self.free_list.pop()
                self.refcount[p] -= 1
                self.refcount[q] += 1
                table[li] = q
                swapped += 1
        finally:
            if swapped:
                self._sync_row(seq_id)
        return swapped

    def extend_seq(self, seq_id: int, n_new: int = 1) -> bool:
        old = self.lengths[seq_id]
        length = old + n_new
        need = self.pages_needed(length) - len(self.tables[seq_id])
        if need > 0:
            if (len(self.free_list) < need
                    or self.pages_needed(length) > self.max_pages):
                return False
            for _ in range(need):
                p = self.free_list.pop()
                self.refcount[p] += 1
                self.tables[seq_id].append(p)
            self._sync_row(seq_id)
        # growing into a shared partially-filled page must trigger COW now,
        # before any write lands at positions [old, length)
        self._ensure_writable(seq_id, old, length)
        self.lengths[seq_id] = length
        return True

    def free_seq(self, seq_id: int):
        for p in self.tables.pop(seq_id, []):
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self.free_list.append(p)
                key = self._page_key.pop(p, None)
                if key is not None and self._prefix_index.get(key) == p:
                    del self._prefix_index[key]
        self.lengths.pop(seq_id, None)
        self.prefix_hits.pop(seq_id, None)
        row = self.rows.pop(seq_id, None)
        if row is not None:
            self._free_rows.append(row)
            self.block_tables = self.block_tables.at[row].set(
                jnp.zeros((self.max_pages,), jnp.int32))
            self.seq_lens = self.seq_lens.at[row].set(0)

    # ------------------------------------------------------------ prefix cache
    def register_prefix(self, seq_id: int, tokens):
        """Publish this sequence's full, written pages to the prefix cache
        (call after the prompt KV has actually been written)."""
        table = self.tables[seq_id]
        for i, key in enumerate(self._prefix_keys(tokens)):
            page = table[i]
            # page -> key stays injective: a page already published under a
            # key keeps it (re-keying would leak the old entry at eviction)
            if key not in self._prefix_index and page not in self._page_key:
                self._prefix_index[key] = page
                self._page_key[page] = key

    def export_prefix_index(self) -> tuple[list[int], list[int]]:
        """Live prefix-cache entries as parallel (keys, page_ids) lists in
        deterministic (key-sorted) order — the engine's persistence layer
        (DESIGN.md §16) serializes these alongside the page payloads."""
        items = sorted((k, p) for k, p in self._prefix_index.items()
                       if self.refcount[p] > 0)
        return [k for k, _ in items], [p for _, p in items]

    def adopt_prefix_pages(self, keys) -> list[tuple[int, int]]:
        """Re-seat a persisted prefix index: allocate one *pinned* physical
        page per key (refcount starts at 1 with no owning sequence, so the
        warm set is never evicted) and publish it under that key.  Returns
        ``(key, page_id)`` pairs for the keys actually adopted — the caller
        scatters the matching payloads there.  Keys already present or past
        the free list's capacity are skipped (a chain lookup simply stops at
        its first missing link, so partial adoption is always safe)."""
        adopted: list[tuple[int, int]] = []
        for key in keys:
            key = int(key)
            if key in self._prefix_index or not self.free_list:
                continue
            page = self.free_list.pop()
            self.refcount[page] += 1
            self._prefix_index[key] = page
            self._page_key[page] = key
            adopted.append((key, page))
        return adopted

    # ------------------------------------------------------- offload / restore
    def _gather_pages_local(self, page_ids):
        """Default payload gather for ``alloc_pools=True``: host copies of
        the named physical pages from this cache's own pools
        (``PAGE_AXIS``), scale pools included."""
        self._require_pools()
        idx = np.asarray(page_ids, np.int32)
        tree = {"k_pages": self.k_pages, "v_pages": self.v_pages}
        if self.k_scales is not None:
            tree.update(k_scales=self.k_scales, v_scales=self.v_scales)
        return jax.tree_util.tree_map(
            lambda a: np.asarray(jnp.take(a, idx, axis=PAGE_AXIS)), tree)

    def _scatter_pages_local(self, page_ids, payload):
        """Default payload scatter: write host pages back into this cache's
        pools at the (freshly allocated) physical page ids."""
        self._require_pools()
        idx = jnp.asarray(page_ids, jnp.int32)
        self.k_pages = self.k_pages.at[:, :, idx].set(
            jnp.asarray(payload["k_pages"]))
        self.v_pages = self.v_pages.at[:, :, idx].set(
            jnp.asarray(payload["v_pages"]))
        if self.k_scales is not None:
            self.k_scales = self.k_scales.at[:, :, idx].set(
                jnp.asarray(payload["k_scales"]))
            self.v_scales = self.v_scales.at[:, :, idx].set(
                jnp.asarray(payload["v_scales"]))

    def offload(self, seq_id: int,
                gather: Optional[Callable] = None) -> OffloadedSeq:
        """Swap a live sequence out to host memory and release everything
        it holds on device (DESIGN.md §14).

        Refcount- and COW-correct: leading *shared* full prefix pages
        (refcount > 1) are released, never copied — their payload stays
        live under the donor's refcount and restore re-shares (or, if the
        donor evicted, recomputes) them.  Private pages covering the rest
        of ``[0, length)`` are copied to host via ``gather(page_ids)``
        (``PAGE_AXIS``; the engine passes a gatherer over its model cache
        tree, ``alloc_pools=True`` caches copy their own pools).  Reserve
        pages past the written extent hold no data and are just released.
        The block-table row, free list and prefix index are left exactly as
        ``free_seq`` leaves them; the checkpoint is recorded in
        ``self.offloaded`` until ``restore`` or ``drop_offloaded``.
        """
        if seq_id in self.offloaded:
            raise ValueError(f"seq {seq_id} is already offloaded")
        table = self.tables[seq_id]
        length = self.lengths[seq_id]
        used = self.pages_needed(length)
        shared = 0
        while shared < used and self.refcount[table[shared]] > 1:
            shared += 1
        for li in range(shared, used):
            # the engine only ever shares leading full prefix pages; a
            # shared page after a private one would be silently lost here
            if self.refcount[table[li]] > 1:
                raise RuntimeError(
                    f"seq {seq_id}: shared page at logical index {li} after "
                    f"private pages — offload supports leading-prefix "
                    f"sharing only")
        snap_ids = table[shared:used]
        payload = None
        nbytes = 0
        if snap_ids:
            gather = gather if gather is not None else self._gather_pages_local
            payload = gather(list(snap_ids))
            nbytes = sum(leaf.nbytes
                         for leaf in jax.tree_util.tree_leaves(payload))
        rec = OffloadedSeq(seq_id=seq_id, length=length, shared_pages=shared,
                           payload=payload, n_payload_pages=len(snap_ids),
                           nbytes=nbytes)
        self.free_seq(seq_id)
        self.offloaded[seq_id] = rec
        return rec

    def restore(self, seq_id: int, tokens, *, reserve: int = 0,
                scatter: Optional[Callable] = None) -> Optional[RestoredSeq]:
        """Bring an offloaded sequence back on device.

        ``tokens`` must be the full context the checkpoint covers (prompt +
        generated-so-far) — it drives hashed-prefix re-sharing through the
        normal ``alloc_seq`` path, so prefix pages that survived on device
        are shared again instead of re-materialized.  The host snapshot is
        scattered into the freshly allocated private pages; logical pages
        ``[hit_pages, snap_start_page)`` — prefix pages whose donor evicted
        while this sequence was off-device — come back *empty* and the
        caller must recompute that token span (the engine re-prefills it).
        Returns None (checkpoint kept, no state change) when pages or rows
        are unavailable; the caller retries later.
        """
        rec = self.offloaded[seq_id]
        if len(tokens) != rec.length:
            raise ValueError(
                f"restore of seq {seq_id} got {len(tokens)} tokens but the "
                f"checkpoint covers {rec.length}")
        if not self.alloc_seq(seq_id, rec.length, tokens=list(tokens),
                              reserve=reserve):
            return None
        hit = self.prefix_hits.get(seq_id, 0)
        used = self.pages_needed(rec.length)
        start = max(hit, rec.shared_pages)
        restored = 0
        if start < used:
            dest = self.tables[seq_id][start:used]
            off = start - rec.shared_pages
            payload = jax.tree_util.tree_map(
                lambda a: a[:, :, off:off + len(dest)], rec.payload)
            scatter = (scatter if scatter is not None
                       else self._scatter_pages_local)
            scatter(list(dest), payload)
            restored = len(dest)
        del self.offloaded[seq_id]
        return RestoredSeq(hit_pages=hit, snap_start_page=rec.shared_pages,
                           length=rec.length, restored_pages=restored)

    def drop_offloaded(self, seq_id: int) -> Optional[OffloadedSeq]:
        """Discard a checkpoint without restoring (aborted while
        preempted)."""
        return self.offloaded.pop(seq_id, None)

    @property
    def offloaded_bytes(self) -> int:
        """Host bytes currently held by offloaded checkpoints."""
        return sum(rec.nbytes for rec in self.offloaded.values())

    # -------------------------------------------------------------- data path
    def _require_pools(self):
        if self.k_pages is None:
            raise RuntimeError(
                "PagedCache(alloc_pools=False) is bookkeeping-only: page "
                "payloads live in the engine's model cache tree, not here")

    def _ensure_writable(self, seq_id: int, start: int, end: int):
        """Copy-on-write: any page covering [start, end) that is shared
        (refcount > 1) is replaced by a private copy before writes land."""
        if end <= start:
            return
        table = self.tables[seq_id]
        dirty = False
        try:
            for li in range(start // self.page_size,
                            (end - 1) // self.page_size + 1):
                p = table[li]
                if self.refcount[p] > 1:
                    # engine flow shares only full, never-rewritten prefix
                    # pages, so COW is unreachable with alloc_pools=False
                    self._require_pools()
                    if not self.free_list:
                        raise RuntimeError(
                            "page pool exhausted during copy-on-write")
                    q = self.free_list.pop()
                    self.k_pages = self.k_pages.at[:, :, q].set(
                        self.k_pages[:, :, p])
                    self.v_pages = self.v_pages.at[:, :, q].set(
                        self.v_pages[:, :, p])
                    if self.k_scales is not None:
                        # scales travel with their pages: a COW'd payload
                        # dequantized against the donor's scales would be
                        # silently wrong after the follower rewrites either
                        self.k_scales = self.k_scales.at[:, :, q].set(
                            self.k_scales[:, :, p])
                        self.v_scales = self.v_scales.at[:, :, q].set(
                            self.v_scales[:, :, p])
                    self.refcount[p] -= 1
                    self.refcount[q] += 1
                    table[li] = q
                    dirty = True
        finally:
            # a partial COW (pool exhausted mid-loop) must still publish the
            # pages it did remap, or the device table would alias stale pages
            if dirty:
                self._sync_row(seq_id)

    @property
    def quantized(self) -> bool:
        return self.kv_quant is not None and self.kv_quant.quantized

    def _write_page_mode(self, seq_id: int, start: int,
                         k: jnp.ndarray, v: jnp.ndarray, layers):
        """Per-page-granularity write: each touched page is requantized over
        its whole valid extent with one (layer, page, head) scale — existing
        tokens are dequantized against the old scale, overlaid with the new
        span, and requantized (so appends into a partially-filled page keep
        one coherent scale; the extra rounding is the storage trade-off of
        per-page scales, DESIGN.md §12).  k, v: (len(layers), n, Hkv, D)."""
        ps = self.page_size
        n = k.shape[1]
        table = self.tables[seq_id]
        length = self.lengths[seq_id]
        lsel = jnp.asarray(layers, jnp.int32)
        k = jnp.swapaxes(k.astype(jnp.float32), 1, 2)     # (L, Hkv, n, D)
        v = jnp.swapaxes(v.astype(jnp.float32), 1, 2)
        for li in range(start // ps, (start + n - 1) // ps + 1):
            p = table[li]
            lo = li * ps
            valid = max(0, min(length, lo + ps) - lo)
            # (L, Hkv, ps, D) payloads under (L, Hkv) page scales
            kf = KQ.dequantize(self.k_pages[lsel][:, :, p],
                               self.k_scales[lsel][:, :, p])
            vf = KQ.dequantize(self.v_pages[lsel][:, :, p],
                               self.v_scales[lsel][:, :, p])
            a, bnd = max(start, lo), min(start + n, lo + ps)
            kf = kf.at[:, :, a - lo:bnd - lo].set(
                k[:, :, a - start:bnd - start])
            vf = vf.at[:, :, a - lo:bnd - lo].set(
                v[:, :, a - start:bnd - start])
            # zero positions past the valid extent: stale payloads from a
            # recycled page must not inflate the page's amax
            mask = (jnp.arange(ps) < valid)[None, None, :, None]
            kq, ks = KQ.quantize(kf * mask, axes=(2, 3),
                                 scale_dtype=self.k_scales.dtype)
            vq, vs = KQ.quantize(vf * mask, axes=(2, 3),
                                 scale_dtype=self.v_scales.dtype)
            self.k_pages = self.k_pages.at[lsel, :, p].set(kq)
            self.v_pages = self.v_pages.at[lsel, :, p].set(vq)
            self.k_scales = self.k_scales.at[lsel, :, p].set(ks)
            self.v_scales = self.v_scales.at[lsel, :, p].set(vs)

    def write_tokens(self, seq_id: int, layer: int, start: int,
                     k: jnp.ndarray, v: jnp.ndarray):
        """k, v: (n, Hkv, D) written at logical positions [start, start+n).

        One batched scatter per (layer, call) — the seed's per-token
        ``.at[page, off].set()`` Python loop dispatched O(n) device ops.
        Shared pages are copy-on-write-resolved first.  Quantized caches
        scatter int8 payloads plus their scales (per-token granularity) or
        requantize the touched pages (per-page granularity).
        """
        self._require_pools()
        n = k.shape[0]
        self._ensure_writable(seq_id, start, start + n)
        if self.quantized and self.kv_quant.granularity == "page":
            self._write_page_mode(seq_id, start, k[None], v[None], [layer])
            return
        table = np.asarray(self.tables[seq_id], np.int32)
        pos = np.arange(start, start + n)
        pages = jnp.asarray(table[pos // self.page_size])
        offs = jnp.asarray(pos % self.page_size)
        # (layer, :, pages, offs) selects an (n, Hkv[, D]) block: the
        # advanced indices' axis leads, matching k/v and their scales
        if self.quantized:
            kq, ks = KQ.quantize(k, scale_dtype=self.k_scales.dtype)
            vq, vs = KQ.quantize(v, scale_dtype=self.v_scales.dtype)
            self.k_pages = self.k_pages.at[layer, :, pages, offs].set(kq)
            self.v_pages = self.v_pages.at[layer, :, pages, offs].set(vq)
            self.k_scales = self.k_scales.at[layer, :, pages, offs].set(ks)
            self.v_scales = self.v_scales.at[layer, :, pages, offs].set(vs)
        else:
            self.k_pages = self.k_pages.at[layer, :, pages, offs].set(
                k.astype(self.dtype))
            self.v_pages = self.v_pages.at[layer, :, pages, offs].set(
                v.astype(self.dtype))

    def write_prefill(self, seq_id: int, start: int,
                      k: jnp.ndarray, v: jnp.ndarray):
        """All-layer prefill write: k, v (n_layers, n, Hkv, D) at logical
        positions [start, start+n) — one scatter per pool for every layer."""
        self._require_pools()
        n = k.shape[1]
        self._ensure_writable(seq_id, start, start + n)
        if self.quantized and self.kv_quant.granularity == "page":
            self._write_page_mode(seq_id, start, k, v, range(self.n_layers))
            return
        table = np.asarray(self.tables[seq_id], np.int32)
        pos = np.arange(start, start + n)
        pages = jnp.asarray(table[pos // self.page_size])
        offs = jnp.asarray(pos % self.page_size)
        # (:, :, pages, offs) selects (n_layers, Hkv, n[, D]): token axis
        # after the head axis
        if self.quantized:
            kq, ks = KQ.quantize(k, scale_dtype=self.k_scales.dtype)
            vq, vs = KQ.quantize(v, scale_dtype=self.v_scales.dtype)
            self.k_pages = self.k_pages.at[:, :, pages, offs].set(
                jnp.swapaxes(kq, 1, 2))
            self.v_pages = self.v_pages.at[:, :, pages, offs].set(
                jnp.swapaxes(vq, 1, 2))
            self.k_scales = self.k_scales.at[:, :, pages, offs].set(
                jnp.swapaxes(ks, 1, 2))
            self.v_scales = self.v_scales.at[:, :, pages, offs].set(
                jnp.swapaxes(vs, 1, 2))
        else:
            self.k_pages = self.k_pages.at[:, :, pages, offs].set(
                jnp.swapaxes(k, 1, 2).astype(self.dtype))
            self.v_pages = self.v_pages.at[:, :, pages, offs].set(
                jnp.swapaxes(v, 1, 2).astype(self.dtype))

    def write_decode_token(self, seq_id: int, k: jnp.ndarray, v: jnp.ndarray):
        """Append one decode token's KV across every layer in one fused
        scatter.  k, v: (n_layers, Hkv, D); the token lands at position
        ``lengths[seq_id] - 1`` (call ``extend_seq`` first)."""
        self._require_pools()
        pos = self.lengths[seq_id] - 1
        if self.quantized and self.kv_quant.granularity == "page":
            self._write_page_mode(seq_id, pos, k[:, None], v[:, None],
                                  range(self.n_layers))
            return
        page = self.tables[seq_id][pos // self.page_size]
        off = pos % self.page_size
        if self.quantized:
            kq, ks = KQ.quantize(k, scale_dtype=self.k_scales.dtype)
            vq, vs = KQ.quantize(v, scale_dtype=self.v_scales.dtype)
            self.k_pages = self.k_pages.at[:, :, page, off].set(kq)
            self.v_pages = self.v_pages.at[:, :, page, off].set(vq)
            self.k_scales = self.k_scales.at[:, :, page, off].set(ks)
            self.v_scales = self.v_scales.at[:, :, page, off].set(vs)
        else:
            self.k_pages = self.k_pages.at[:, :, page, off].set(
                k.astype(self.dtype))
            self.v_pages = self.v_pages.at[:, :, page, off].set(
                v.astype(self.dtype))

    def gather_kv(self, seq_id: int, layer: int):
        """Returns (k, v): (len, Hkv, D) gathered via the block table —
        dequantized to ``compute_dtype`` when the pools store int8."""
        self._require_pools()
        table = jnp.asarray(self.tables[seq_id], jnp.int32)
        length = self.lengths[seq_id]
        k = self.k_pages[layer][:, table]                 # (Hkv, n, ps, D)
        v = self.v_pages[layer][:, table]
        if self.quantized:
            k = KQ.dequantize(k, self.k_scales[layer][:, table],
                              dtype=self.compute_dtype)
            v = KQ.dequantize(v, self.v_scales[layer][:, table],
                              dtype=self.compute_dtype)
        k = k.transpose(1, 2, 0, 3).reshape(-1, self.kv_heads, self.head_dim)
        v = v.transpose(1, 2, 0, 3).reshape(-1, self.kv_heads, self.head_dim)
        return k[:length], v[:length]

    # -------------------------------------------- speculative write rollback
    def spec_snapshot(self, seq_id: int) -> dict:
        """Checkpoint the state a k-token speculative write can disturb
        (data-path API, ``alloc_pools=True``): the payload bytes of the
        partially-filled tail page plus the current length/table extents.

        Per-token scales (and fp passthrough) don't strictly need the
        payload copy — positions past ``lengths`` are never read, so length
        rollback alone is lossless.  Per-*page* scales do: appending into a
        page requantizes the whole page against a new amax, so the retained
        prefix's bytes change.  ``truncate_seq(..., snapshot=...)``
        restores those bytes exactly, which is what makes the per-page
        requantize write path round-trip a rollback losslessly: re-writing
        the accepted tokens afterwards performs the identical
        dequant-overlay-requant computation a non-speculative append would
        have, byte for byte (tested)."""
        self._require_pools()
        length = self.lengths[seq_id]
        tail: Optional[dict] = None
        tail_page = None
        if length % self.page_size:
            tail_page = self.tables[seq_id][length // self.page_size]
            tail = self._gather_pages_local([tail_page])
        return {"length": length, "n_table": len(self.tables[seq_id]),
                "tail_page": tail_page, "tail": tail}

    def truncate_seq(self, seq_id: int, snapshot: dict) -> None:
        """Roll a speculative extension back to the snapshot: restore the
        tail page's payload bytes, free pages allocated past the snapshot's
        table extent, and reset ``lengths``.  The caller then re-appends
        the *accepted* tokens through the normal write path — under
        per-page scales that reproduces exactly the bytes of having only
        ever written them."""
        self._require_pools()
        if self.lengths[seq_id] < snapshot["length"]:
            raise ValueError(
                f"seq {seq_id} is shorter ({self.lengths[seq_id]}) than its "
                f"snapshot ({snapshot['length']}) — nothing to roll back")
        table = self.tables[seq_id]
        while len(table) > snapshot["n_table"]:
            p = table.pop()
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self.free_list.append(p)
        if snapshot["tail"] is not None:
            # the tail page cannot have been COW-swapped meanwhile: spec
            # writes went through _ensure_writable, so it is private — but
            # its *identity* may differ from the snapshot's if a COW fired
            # during the speculative write; restore into the current page
            cur = table[snapshot["length"] // self.page_size]
            self._scatter_pages_local([cur], snapshot["tail"])
        self.lengths[seq_id] = snapshot["length"]
        self._sync_row(seq_id)
