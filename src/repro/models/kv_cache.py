"""Decode-state containers and the paged KV pool.

Two layouts exist, used at different altitudes of the system:

  * **Contiguous slot cache** (``AttnKV``) — fixed (G, B, S, KV, D)
    arrays threaded through the jitted decode step.  This is what the
    dry-run lowers and what the roofline reads; it is also the device-
    side cache of the serving engine (one slot per active request).
  * **Paged pool** (``PagedKVPool``) — vLLM-style page table over a
    host-memory pool, used by the host attention backend for
    CPU-offloaded requests (the paper's CPU tier).  Implemented in
    numpy because it lives on the host by construction.

``StackState`` bundles the per-pattern-entry states for the scanned
block stack; every leaf carries a leading ``G`` (scan groups) axis.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.compression import (compress_page_bytes,
                                           decompress_page_bytes,
                                           dequantize_kv_rows,
                                           quantize_kv_rows)


class AttnKV(NamedTuple):
    """Contiguous KV slots for one attention entry, stacked over groups.

    k, v: (G, B, S, KV, D); grows by writing at index ``lengths``.
    """

    k: jnp.ndarray
    v: jnp.ndarray


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StackState:
    """Decode state of the whole block stack.

    ``per_entry`` is a tuple over pattern entries; each element is a
    state pytree whose leaves are stacked over the G scan groups (or
    ``None`` for stateless entries).  ``lengths`` is (B,) int32 — the
    number of tokens already cached per sequence.
    """

    per_entry: Tuple[Any, ...]
    lengths: jnp.ndarray


def write_kv(kv: AttnKV, g: jnp.ndarray, k_new: jnp.ndarray, v_new: jnp.ndarray,
             lengths: jnp.ndarray) -> AttnKV:
    """Write one new token's K/V for group ``g`` at per-row positions.

    k_new, v_new: (B, 1, KV, D); lengths: (B,).
    """
    b = k_new.shape[0]
    rows = jnp.arange(b)
    k = kv.k.at[g, rows, lengths].set(k_new[:, 0].astype(kv.k.dtype))
    v = kv.v.at[g, rows, lengths].set(v_new[:, 0].astype(kv.v.dtype))
    return AttnKV(k=k, v=v)


def write_kv_span(kv: AttnKV, g: jnp.ndarray, k_new: jnp.ndarray,
                  v_new: jnp.ndarray, start: jnp.ndarray) -> AttnKV:
    """Write a T-token span (prefill).  k_new: (B, T, KV, D); start: (B,)."""
    b, t = k_new.shape[:2]
    rows = jnp.arange(b)[:, None]
    cols = start[:, None] + jnp.arange(t)[None, :]
    k = kv.k.at[g, rows, cols].set(k_new.astype(kv.k.dtype))
    v = kv.v.at[g, rows, cols].set(v_new.astype(kv.v.dtype))
    return AttnKV(k=k, v=v)


# ---------------------------------------------------------------------------
# Host-side paged KV pool (the paper's CPU tier)
# ---------------------------------------------------------------------------


class PagedKVPool:
    """Paged KV storage in host memory, one pool shared by all layers.

    Layout: ``pages[2, num_pages, page_size, kv_heads, head_dim]``
    (index 0 = K, 1 = V).  Each (request, layer) owns a chain of pages
    recorded in ``page_tables``.  Allocation is a simple free list —
    deterministic and O(1) — matching vLLM's block allocator.

    Page-chain mutation (``allocate``/``extend``/``free``) is guarded
    by a lock: the serving engine reserves chains at admission time on
    its own thread while the host executor's in-flight job may extend
    a chain concurrently.  ``can_admit`` stays an advisory lock-free
    read — callers must tolerate ``allocate`` raising ``MemoryError``
    if a concurrent extension consumed the pages in between.

    Pages are **refcounted**: ``fork`` aliases the leading pages of one
    owner's chains into a new owner (the prefix cache sharing a cached
    prefix with an admission — zero copies), and every write path is
    copy-on-write — a page with refcount > 1 is copied to a fresh page
    before mutation, so shared prefix KV is never clobbered in place.
    Owners registered via ``mark_evictable`` (prefix-cache entries, not
    live requests) form an LRU: ``allocate``/``extend``/COW reclaim
    them automatically under memory pressure, notifying ``on_evict`` so
    the cache index can drop the entry.

    **Precision.** With ``host_kv_dtype="int8"`` pages store symmetric
    int8 with one fp32 scale per (K|V, page, slot) — i.e. per token row
    — in a side table indexed by physical page, so COW copies and
    ``fork`` aliases carry their scales by page identity automatically.
    ``gather`` and the host attention kernel dequantize on the fly; the
    pool never materializes a full-precision copy of itself.  Per-row
    scaling also makes requantizing a dequantized row reproduce the
    identical int8 codes (the max-magnitude element maps back to ±127),
    so gather → write_prompt chains are stable.

    **Cold pages.** With ``cold_page_compress_after > 0`` pages whose
    owner has been idle past that many seconds are losslessly
    compressed (zstd): the raw page bytes
    (and scale rows) move into a side blob dict keyed by a negative
    sentinel id spliced into the page chains, and the physical page
    returns to the free list — that is the capacity win, since the pool
    array is preallocated.  Any touch (write, gather, ``ensure_hot``)
    transparently rehydrates.  Allocation pressure prefers compressing
    evictable owners' pages over evicting them (the degradation
    ladder's cheaper rung).
    """

    def __init__(self, num_pages: int, page_size: int, num_layers: int,
                 kv_heads: int, head_dim: int, dtype=np.float32,
                 host_kv_dtype: str = "fp32",
                 cold_page_compress_after: float = 0.0) -> None:
        if host_kv_dtype not in ("fp32", "int8"):
            raise ValueError(f"host_kv_dtype must be fp32|int8, "
                             f"got {host_kv_dtype!r}")
        self.page_size = page_size
        self.num_layers = num_layers
        self.host_kv_dtype = host_kv_dtype
        self.quantized = host_kv_dtype == "int8"
        # dtype handed back by ``gather`` (and the empty-chain path) —
        # stored dtype is int8 when quantized, but readers see this.
        self.logical_dtype = np.dtype(dtype)
        stored = np.int8 if self.quantized else dtype
        self.pages = np.zeros((2, num_pages, page_size, kv_heads, head_dim),
                              dtype=stored)
        # per-slot symmetric-quantization scales (K|V, page, slot);
        # indexed by physical page so COW/fork carry them for free
        self.scales: Optional[np.ndarray] = (
            np.ones((2, num_pages, page_size), np.float32)
            if self.quantized else None)
        # cold-page compression: sentinel id (< 0) -> compressed blob
        self.cold_page_compress_after = float(cold_page_compress_after)
        self._compressed: Dict[int, bytes] = {}
        self._next_blob_id = -1
        self._last_touch: Dict[int, float] = {}
        self.pages_compressed = 0
        self.pages_decompressed = 0
        self.compressed_ratio_ewma: Optional[float] = None
        self.free_pages: List[int] = list(range(num_pages - 1, -1, -1))
        # (request_id, layer) -> list of page indices
        self.page_tables: Dict[Tuple[int, int], List[int]] = {}
        # request_id -> token count (same across layers)
        self.lengths: Dict[int, int] = {}
        # physical page -> owners referencing it (absent == free)
        self.page_refs: Dict[int, int] = {}
        # LRU registry of owners the pool may reclaim under pressure
        self._evictable: Dict[int, int] = {}
        self._tick = 0
        self.evictions = 0
        # callback(owner_id) fired after an LRU eviction (outside the
        # allocation lock) so the index holding the owner can forget it
        self.on_evict: Optional[Any] = None
        # chaos hook (repro.serving.faults): called at the top of
        # ``allocate`` and may raise MemoryError to simulate exhaustion.
        # Only ``allocate`` is instrumented — its callers (admission,
        # preemption) tolerate MemoryError; ``extend`` failures mid-
        # decode would be real corruption, not an injectable fault.
        self.fault_hook: Optional[Any] = None
        self._alloc_lock = threading.Lock()

    @property
    def num_free(self) -> int:
        return len(self.free_pages)

    def pages_short(self, total_tokens: int, chain_len: int) -> int:
        """Pages a chain of ``chain_len`` is short of holding
        ``total_tokens`` — the single capacity predicate shared by
        ``extend`` and the bulk/streaming write paths."""
        return max(0, -(-total_tokens // self.page_size) - chain_len)

    def reclaimable_pages(self) -> int:
        """Advisory count of pages LRU eviction could free right now:
        exclusively-owned (refcount 1) pages of evictable owners."""
        total = 0
        for owner in list(self._evictable):
            for layer in range(self.num_layers):
                total += sum(1 for p in self.page_tables.get((owner, layer),
                                                             [])
                             if p >= 0 and self.page_refs.get(p, 1) <= 1)
        return total

    def can_admit(self, tokens: int) -> bool:
        per_layer = -(-tokens // self.page_size)
        return (self.num_free + self.reclaimable_pages()
                >= per_layer * self.num_layers)

    # --- internal helpers (call with ``_alloc_lock`` held) ----------------
    def _free_locked(self, owner: int) -> None:
        for layer in range(self.num_layers):
            for p in self.page_tables.pop((owner, layer), []):
                r = self.page_refs.get(p, 1) - 1
                if r <= 0:
                    self.page_refs.pop(p, None)
                    if p < 0:
                        self._compressed.pop(p, None)
                    else:
                        self.free_pages.append(p)
                else:
                    self.page_refs[p] = r
        self.lengths.pop(owner, None)
        self._evictable.pop(owner, None)
        self._last_touch.pop(owner, None)

    def _compress_page_locked(self, phys: int) -> int:
        """Move physical page ``phys`` into a compressed blob behind a
        fresh negative sentinel id, splice the sentinel into every
        chain referencing it, and return the page to the free list."""
        raw = self.pages[:, phys].tobytes()
        if self.scales is not None:
            raw += self.scales[:, phys].tobytes()
        blob = compress_page_bytes(raw)
        sid = self._next_blob_id
        self._next_blob_id -= 1
        self._compressed[sid] = blob
        self.page_refs[sid] = self.page_refs.pop(phys, 1)
        for chain in self.page_tables.values():
            for i, p in enumerate(chain):
                if p == phys:
                    chain[i] = sid
        self.free_pages.append(phys)
        self.pages_compressed += 1
        ratio = len(blob) / max(len(raw), 1)
        self.compressed_ratio_ewma = (
            ratio if self.compressed_ratio_ewma is None
            else 0.8 * self.compressed_ratio_ewma + 0.2 * ratio)
        return sid

    def _fill_from_blob_locked(self, sid: int, phys: int) -> None:
        raw = decompress_page_bytes(self._compressed[sid])
        kv_nbytes = self.pages[:, phys].nbytes
        self.pages[:, phys] = np.frombuffer(
            raw[:kv_nbytes],
            self.pages.dtype).reshape(self.pages[:, phys].shape)
        if self.scales is not None:
            self.scales[:, phys] = np.frombuffer(
                raw[kv_nbytes:], np.float32).reshape(2, self.page_size)

    def _decompress_page_locked(self, sid: int,
                                evicted: List[int]) -> int:
        """Rehydrate sentinel ``sid`` into a fresh physical page,
        splicing it back into every chain (refcount transfers whole:
        sharers keep sharing the hot page)."""
        evicted += self._reclaim_locked(1)
        if not self.free_pages:
            raise MemoryError("paged pool exhausted rehydrating "
                              "compressed page")
        fresh = self.free_pages.pop()
        self._fill_from_blob_locked(sid, fresh)
        del self._compressed[sid]
        self.page_refs[fresh] = self.page_refs.pop(sid, 1)
        for chain in self.page_tables.values():
            for i, p in enumerate(chain):
                if p == sid:
                    chain[i] = fresh
        self.pages_decompressed += 1
        return fresh

    def _reclaim_locked(self, need: int) -> List[int]:
        """Free pages until ``need`` exist: first compress evictable
        owners' exclusively-owned pages in place (when cold-page
        compression is enabled — the entry survives, only colder),
        then LRU-evict whole owners.  Returns the evicted owners; the
        caller fires ``on_evict`` after releasing the lock."""
        evicted: List[int] = []
        if self.cold_page_compress_after > 0 \
                and len(self.free_pages) < need:
            for owner in sorted(self._evictable,
                                key=self._evictable.get):
                if len(self.free_pages) >= need:
                    break
                for layer in range(self.num_layers):
                    for p in list(self.page_tables.get((owner, layer), [])):
                        if p >= 0 and self.page_refs.get(p, 1) <= 1:
                            self._compress_page_locked(p)
                            if len(self.free_pages) >= need:
                                break
                    if len(self.free_pages) >= need:
                        break
        while len(self.free_pages) < need and self._evictable:
            owner = min(self._evictable, key=self._evictable.get)
            self._free_locked(owner)
            evicted.append(owner)
            self.evictions += 1
        return evicted

    def _notify(self, evicted: List[int]) -> None:
        if self.on_evict is not None:
            for owner in evicted:
                self.on_evict(owner)

    def allocate(self, request_id: int, tokens: int) -> None:
        """Reserve page chains for a new request with `tokens` capacity."""
        if self.fault_hook is not None:
            self.fault_hook()
        per_layer = -(-tokens // self.page_size)
        need = per_layer * self.num_layers
        evicted: List[int] = []
        try:
            with self._alloc_lock:
                evicted = self._reclaim_locked(need)
                if self.num_free < need:
                    raise MemoryError("paged pool exhausted")
                for layer in range(self.num_layers):
                    chain = [self.free_pages.pop() for _ in range(per_layer)]
                    for p in chain:
                        self.page_refs[p] = 1
                    self.page_tables[(request_id, layer)] = chain
                self.lengths[request_id] = 0
                self._touch_owner(request_id)
        finally:
            self._notify(evicted)

    def extend(self, request_id: int, extra_tokens: int) -> None:
        """Grow every layer's chain to hold lengths + extra_tokens."""
        cur = self.lengths[request_id]
        evicted: List[int] = []
        try:
            with self._alloc_lock:
                chain_len = len(self.page_tables[(request_id, 0)])
                need = self.pages_short(cur + extra_tokens, chain_len)
                evicted = self._reclaim_locked(need * self.num_layers)
                if need * self.num_layers > self.num_free:
                    raise MemoryError("paged pool exhausted on extend")
                if need:
                    for layer in range(self.num_layers):
                        grown = [self.free_pages.pop() for _ in range(need)]
                        for p in grown:
                            self.page_refs[p] = 1
                        self.page_tables[(request_id, layer)].extend(grown)
        finally:
            self._notify(evicted)

    # --- prefix-cache surface: sharing, adoption, LRU ---------------------
    def fork(self, src_owner: int, dst_id: int, tokens: int) -> None:
        """Alias the pages holding ``src_owner``'s first ``tokens``
        positions into new owner ``dst_id`` (refcount++, zero copies).
        The new owner starts at length ``tokens``; any write it later
        lands in a shared page goes through copy-on-write, so the
        source's cached KV is never mutated in place."""
        per_layer = -(-tokens // self.page_size)
        with self._alloc_lock:
            for layer in range(self.num_layers):
                shared = self.page_tables[(src_owner, layer)][:per_layer]
                self.page_tables[(dst_id, layer)] = list(shared)
                for p in shared:
                    self.page_refs[p] = self.page_refs.get(p, 1) + 1
            self.lengths[dst_id] = tokens
            if self.cold_page_compress_after > 0:
                self._last_touch[dst_id] = time.monotonic()

    def mark_evictable(self, owner: int) -> None:
        """Register ``owner`` with the LRU — the pool may reclaim its
        exclusively-owned pages under allocation pressure."""
        with self._alloc_lock:
            self._tick += 1
            self._evictable[owner] = self._tick

    def touch(self, owner: int) -> None:
        """Refresh ``owner``'s LRU position (a cache hit)."""
        with self._alloc_lock:
            if owner in self._evictable:
                self._tick += 1
                self._evictable[owner] = self._tick

    def owner_pages(self, owner: int) -> int:
        """Pages referenced by ``owner`` across all layer chains."""
        return sum(len(self.page_tables.get((owner, layer), []))
                   for layer in range(self.num_layers))

    @property
    def page_bytes(self) -> int:
        """Bytes of one physical page as stored (K + V at the stored
        element size, plus the page's scale rows when quantized) — the
        byte cost capacity predicates and byte gauges should charge."""
        per = int(self.pages[0, 0].nbytes) * 2
        if self.scales is not None:
            per += int(self.scales[:, 0].nbytes)
        return per

    @property
    def kv_dtype_bytes(self) -> int:
        """Stored bytes per KV element (1 for int8, 4 for fp32)."""
        return int(self.pages.dtype.itemsize)

    @property
    def has_compressed(self) -> bool:
        """Advisory lock-free check for any cold compressed page."""
        return bool(self._compressed)

    def byte_stats(self) -> Dict[str, int]:
        """Host-pool byte accounting: hot (occupied physical pages),
        compressed (cold blob bytes), free (unoccupied physical)."""
        num_pages = self.pages.shape[1]
        pb = self.page_bytes
        free = len(self.free_pages)
        comp = sum(len(b) for b in self._compressed.values())
        return {"hot": (num_pages - free) * pb, "compressed": comp,
                "free": free * pb}

    def ensure_hot(self, owner: int) -> None:
        """Rehydrate every compressed page in ``owner``'s chains so
        readers (host attention, gather) see physical page ids."""
        if not self._compressed:
            return
        evicted: List[int] = []
        try:
            with self._alloc_lock:
                for layer in range(self.num_layers):
                    chain = self.page_tables.get((owner, layer), [])
                    for p in list(chain):
                        if p < 0:
                            self._decompress_page_locked(p, evicted)
        finally:
            self._notify(evicted)

    def maybe_compress_cold(self, now: Optional[float] = None) -> int:
        """Compress exclusively-owned pages of owners idle past
        ``cold_page_compress_after`` seconds.  Called periodically by
        the engine; returns the number of pages compressed."""
        if self.cold_page_compress_after <= 0:
            return 0
        now = time.monotonic() if now is None else now
        count = 0
        with self._alloc_lock:
            for owner, ts in list(self._last_touch.items()):
                if now - ts < self.cold_page_compress_after:
                    continue
                for layer in range(self.num_layers):
                    for p in list(self.page_tables.get((owner, layer), [])):
                        if p >= 0 and self.page_refs.get(p, 1) <= 1:
                            self._compress_page_locked(p)
                            count += 1
        return count

    def _touch_owner(self, owner: int) -> None:
        if self.cold_page_compress_after > 0:
            self._last_touch[owner] = time.monotonic()

    def _writable_page(self, request_id: int, layer: int,
                       page_idx: int) -> int:
        """The physical page backing ``chain[page_idx]``, copied to a
        fresh exclusively-owned page first when shared (copy-on-write)
        and rehydrated first when compressed.  Every write path funnels
        through here so refcount-shared pages are never mutated in
        place."""
        chain = self.page_tables[(request_id, layer)]
        page = chain[page_idx]
        if page >= 0 and self.page_refs.get(page, 1) <= 1:
            return page
        evicted: List[int] = []
        try:
            with self._alloc_lock:
                page = chain[page_idx]   # may have changed before lock
                if page < 0:
                    if self.page_refs.get(page, 1) <= 1:
                        return self._decompress_page_locked(page, evicted)
                    # shared compressed page: private hot copy for this
                    # chain, blob stays for the other sharers
                    evicted += self._reclaim_locked(1)
                    if not self.free_pages:
                        raise MemoryError(
                            "paged pool exhausted on copy-on-write")
                    fresh = self.free_pages.pop()
                    self._fill_from_blob_locked(page, fresh)
                    self.page_refs[fresh] = 1
                    self.page_refs[page] -= 1
                    chain[page_idx] = fresh
                    return fresh
                if self.page_refs.get(page, 1) <= 1:
                    return page           # lost the race: now exclusive
                evicted += self._reclaim_locked(1)
                if not self.free_pages:
                    raise MemoryError("paged pool exhausted on copy-on-write")
                if self.page_refs.get(page, 1) <= 1:
                    return page           # reclaim released the sharer
                fresh = self.free_pages.pop()
                self.pages[:, fresh] = self.pages[:, page]
                if self.scales is not None:
                    self.scales[:, fresh] = self.scales[:, page]
                self.page_refs[fresh] = 1
                self.page_refs[page] -= 1
                chain[page_idx] = fresh
                return fresh
        finally:
            self._notify(evicted)

    def append(self, request_id: int, layer: int, k: np.ndarray,
               v: np.ndarray, advance: bool) -> None:
        """Append one token's K/V for (request, layer).

        ``advance`` bumps the shared length counter (pass True exactly
        once per token, on the last layer written).
        """
        pos = self.lengths[request_id]
        chain = self.page_tables[(request_id, layer)]
        page_idx = pos // self.page_size
        if page_idx >= len(chain):
            self.extend(request_id, 1)
        page = self._writable_page(request_id, layer, page_idx)
        slot = pos % self.page_size
        if self.quantized:
            qk, sk = quantize_kv_rows(np.asarray(k, np.float32)[None])
            qv, sv = quantize_kv_rows(np.asarray(v, np.float32)[None])
            self.pages[0, page, slot] = qk[0]
            self.pages[1, page, slot] = qv[0]
            self.scales[0, page, slot] = sk[0]
            self.scales[1, page, slot] = sv[0]
        else:
            self.pages[0, page, slot] = k
            self.pages[1, page, slot] = v
        self._touch_owner(request_id)
        if advance:
            self.lengths[request_id] = pos + 1

    def write_prompt(self, request_id: int, layer: int, k: np.ndarray,
                     v: np.ndarray, advance: bool) -> None:
        """Bulk-write a prompt's K/V (T, kv_heads, head_dim) for one
        layer: one strided write per page span, no per-token loop."""
        t = k.shape[0]
        start = self.lengths[request_id]
        chain = self.page_tables[(request_id, layer)]
        if self.pages_short(start + t, len(chain)):
            self.extend(request_id, t)
        sk = sv = None
        if self.quantized:
            k, sk = quantize_kv_rows(np.asarray(k, np.float32))
            v, sv = quantize_kv_rows(np.asarray(v, np.float32))
        off = 0
        while off < t:
            pos = start + off
            page = self._writable_page(request_id, layer,
                                       pos // self.page_size)
            slot = pos % self.page_size
            span = min(self.page_size - slot, t - off)
            self.pages[0, page, slot:slot + span] = k[off:off + span]
            self.pages[1, page, slot:slot + span] = v[off:off + span]
            if self.quantized:
                self.scales[0, page, slot:slot + span] = sk[off:off + span]
                self.scales[1, page, slot:slot + span] = sv[off:off + span]
            off += span
        self._touch_owner(request_id)
        if advance:
            self.lengths[request_id] = start + t

    def append_rows(self, request_ids, layer: int, positions: np.ndarray,
                    k: np.ndarray, v: np.ndarray) -> None:
        """Vectorized one-token-per-request append at explicit positions
        (the host cohort's per-layer write): a single fancy-index store
        for the whole batch instead of a Python loop of row writes.

        k, v: (B, kv_heads, head_dim); positions: (B,) — the in-flight
        token's position per request (``lengths`` is NOT advanced; call
        ``lengths[rid] += 1`` / the executor's token-boundary hook once
        the token's final layer is written).
        """
        ps = self.page_size
        positions = np.asarray(positions, np.int64)
        pages = np.empty(len(request_ids), np.int64)
        for i, rid in enumerate(request_ids):
            chain = self.page_tables[(rid, layer)]
            page_idx = int(positions[i]) // ps
            if page_idx >= len(chain):
                self.extend(rid, int(positions[i]) + 1 - self.lengths[rid])
            pages[i] = self._writable_page(rid, layer, page_idx)
        if self.quantized:
            k, sk = quantize_kv_rows(np.asarray(k, np.float32))
            v, sv = quantize_kv_rows(np.asarray(v, np.float32))
            self.scales[0, pages, positions % ps] = sk
            self.scales[1, pages, positions % ps] = sv
        self.pages[0, pages, positions % ps] = k
        self.pages[1, pages, positions % ps] = v
        if self.cold_page_compress_after > 0:
            now = time.monotonic()
            for rid in request_ids:
                self._last_touch[rid] = now

    def gather(self, request_id: int, layer: int,
               n: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Materialize (K, V) of shape (len, kv_heads, head_dim) in the
        *logical* dtype (dequantized when the pool stores int8) —
        optionally only the first ``n`` positions (a truncated
        prefix-cache hit).  Compressed pages rehydrate transparently."""
        total = self.lengths[request_id]
        n = total if n is None else min(n, total)
        chain = self.page_tables[(request_id, layer)]
        npages = -(-n // self.page_size)
        if any(p < 0 for p in chain[:npages]):
            self.ensure_hot(request_id)
            chain = self.page_tables[(request_id, layer)]
        self._touch_owner(request_id)
        if n == 0:
            kv_heads, head_dim = self.pages.shape[-2:]
            empty = np.zeros((0, kv_heads, head_dim), self.logical_dtype)
            return empty, empty.copy()
        idx = np.asarray(chain[:npages], np.int64)
        kv_heads, head_dim = self.pages.shape[-2:]
        k = self.pages[0, idx].reshape(-1, kv_heads, head_dim)[:n]
        v = self.pages[1, idx].reshape(-1, kv_heads, head_dim)[:n]
        if self.scales is not None:
            k = dequantize_kv_rows(k, self.scales[0, idx].reshape(-1)[:n])
            v = dequantize_kv_rows(v, self.scales[1, idx].reshape(-1)[:n])
        return k, v

    def free(self, request_id: int) -> None:
        """Drop an owner: refcounts decrement, exclusively-owned pages
        return to the free list (pages still shared with another owner
        survive — no double free by construction)."""
        with self._alloc_lock:
            self._free_locked(request_id)
