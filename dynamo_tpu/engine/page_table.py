"""Host-side paged-KV allocator with content-addressed prefix caching.

The device holds one flat page pool (models/llama.py KVPages); this module
owns which page belongs to whom. Three ideas:

1. **Ref-counted pages**: a page can back multiple sequences when they share
   a prefix (same chained block hash ⇒ byte-identical KV).
2. **Prefix cache**: full pages are registered under their TokenBlock
   sequence hash; new requests reuse any cached prefix chain. Freed pages
   stay cached (refcount 0) in an LRU until reclaimed.
3. **KV events**: every cache store/remove emits an event for the KV-aware
   router's global index (parity with the reference's engine-emitted KV
   events — /root/reference lib/llm/src/kv_router/publisher.rs; vLLM's ZMQ
   event stream — and the mocker's KvManager, mocker/kv_manager.rs:121).

Page 0 is the null page (padding writes), never allocated.

The bookkeeping core (free list, refcounts, hash maps, LRU reclaim) runs in
C++ when libdynamo_native is available (native/pool.cpp — reference parity
with the native Rust block pool, lib/llm/src/block_manager/pool.rs); the
pure-Python path below is the fallback and the semantic spec. Page metadata
(parent hashes, token payloads for KV events) and stats stay Python-side in
both modes. Tests assert both paths agree on random workloads.
"""

from __future__ import annotations

import ctypes
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Literal, Optional, Sequence

from dynamo_tpu import native


@dataclass(frozen=True)
class KvEvent:
    """Block stored/removed in this worker's KV cache."""

    kind: Literal["stored", "removed"]
    #: chained sequence hashes (tokens/blocks.py) — one per block
    block_hashes: tuple[int, ...]
    #: parent chain hash for "stored" (None at root)
    parent_hash: Optional[int] = None
    #: token payload for stored events (lets indexers rebuild chains)
    token_blocks: tuple[tuple[int, ...], ...] = ()


@dataclass
class PrefixCacheStats:
    queries: int = 0
    hit_tokens: int = 0
    query_tokens: int = 0
    stored_blocks: int = 0
    evicted_blocks: int = 0
    # KVBM tier movement (dynamo_tpu/kvbm) — zero when tiering is off
    offloaded_blocks: int = 0
    onboarded_blocks: int = 0

    @property
    def hit_rate(self) -> float:
        return self.hit_tokens / self.query_tokens if self.query_tokens else 0.0


class PageAllocator:
    """Free-list + refcount + prefix-cache LRU over a fixed page pool."""

    def __init__(
        self,
        num_pages: int,
        page_size: int,
        on_event: Optional[Callable[[KvEvent], None]] = None,
        state_slots: int = 0,
    ):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is the null page)")
        self.num_pages = num_pages
        self.page_size = page_size
        #: the second kind of per-sequence cache (a model with state-space
        #: layers): `state_slots` slots of recurrent state, 1..state_slots
        #: (slot 0 is the null slot, as page 0 is the null page). A request
        #: holds ONE for its life in `running`; the same admission hands it
        #: its pages and its slot (`Scheduler._admit`). With slots, a prefix
        #: hit is refused: cached pages without the recurrent state at
        #: their boundary cannot be continued from (snapshots: ROADMAP R8)
        self.state_slots = state_slots
        self._free_slots_list: list[int] = list(range(state_slots, 0, -1))
        self.slots_watermark = 0  # most slots ever held at once
        self.slots_taken = 0  # admissions that took (and so zeroed) a slot
        self.prefix_hits_refused_state = 0
        #: page id -> (seq_hash, parent_hash, tokens) for registered pages
        self._page_meta: dict[int, tuple[int, Optional[int], tuple[int, ...]]] = {}
        self._on_event = on_event
        self.stats = PrefixCacheStats()
        #: high-watermark of active (referenced) pages since boot — the
        #: pool-pressure gauge the fleet plane exports; updated on every
        #: successful allocation, so peaks between metric refreshes are
        #: still captured
        self.watermark = 0
        self._nlib = native.lib()
        if self._nlib is not None:
            self._np = self._nlib.dyn_pool_new(num_pages)
        else:
            self._np = None
        if self._np is None:
            self._free: list[int] = list(range(num_pages - 1, 0, -1))
            self._refcount: dict[int, int] = {}
            #: full pages registered by content: seq_hash -> page id
            self._by_hash: dict[int, int] = {}
            #: refcount-0 registered pages, LRU order (oldest first)
            self._reclaimable: OrderedDict[int, None] = OrderedDict()

    def __del__(self):
        np_, lib = getattr(self, "_np", None), getattr(self, "_nlib", None)
        if np_ is not None and lib is not None:
            lib.dyn_pool_delete(np_)

    # -- capacity ----------------------------------------------------------

    @property
    def num_free(self) -> int:
        """Pages allocatable right now (free list + reclaimable cache)."""
        if self._np is not None:
            return self._nlib.dyn_pool_num_free(self._np)
        return len(self._free) + len(self._reclaimable)

    @property
    def num_active(self) -> int:
        return (self.num_pages - 1) - self.num_free

    def usage(self) -> float:
        return self.num_active / (self.num_pages - 1)

    def _free_slots(self) -> int:
        """Free-list length — pages allocatable without evicting."""
        if self._np is not None:
            return self._nlib.dyn_pool_free_list_len(self._np)
        return len(self._free)

    def _peek_reclaimable(self, n: int) -> list[int]:
        """The first n pages allocate() would evict (LRU-first)."""
        if n <= 0:
            return []
        if self._np is not None:
            out = (ctypes.c_uint32 * n)()
            got = self._nlib.dyn_pool_peek_reclaimable(self._np, out, n)
            return list(out[:got])
        return list(self._reclaimable)[:n]

    # -- state slots ------------------------------------------------------

    @property
    def num_free_slots(self) -> int:
        return len(self._free_slots_list)

    def allocate_slot(self) -> Optional[int]:
        """A free state slot, or None. Its content is whatever its last
        owner left: a sequence that starts at position 0 starts from zeros
        whatever the slot holds (models/nemotron_h.py)."""
        if not self._free_slots_list:
            return None
        self.slots_taken += 1
        slot = self._free_slots_list.pop()
        self.slots_watermark = max(
            self.slots_watermark, self.state_slots - self.num_free_slots
        )
        return slot

    def free_slot(self, slot: int) -> None:
        if slot in self._free_slots_list or not 0 < slot <= self.state_slots:
            raise ValueError(f"double free of state slot {slot}")
        self._free_slots_list.append(slot)

    # -- allocation --------------------------------------------------------

    def allocate(self, n: int) -> Optional[list[int]]:
        """Get n fresh pages (evicting cached pages LRU-first), or None."""
        if self._np is not None:
            if n > self.num_free:
                return None
            out = (ctypes.c_uint32 * max(1, n))()
            if not self._nlib.dyn_pool_allocate(self._np, n, out):
                return None
            self._drain_evicted()
            self.watermark = max(self.watermark, self.num_active)
            return list(out[:n])
        if n > self.num_free:
            return None
        out_pages = []
        for _ in range(n):
            if self._free:
                page = self._free.pop()
            else:
                page, _ = self._reclaimable.popitem(last=False)
                self._evict(page)
            self._refcount[page] = 1
            out_pages.append(page)
        self.watermark = max(self.watermark, self.num_active)
        return out_pages

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference; registered pages become reclaimable (stay
        cached), unregistered ones return to the free list."""
        if self._np is not None:
            n = len(pages)
            if n == 0:
                return
            arr = (ctypes.c_uint32 * n)(*pages)
            bad = self._nlib.dyn_pool_release(self._np, arr, n)
            if bad >= 0:
                raise ValueError(f"double free of page {pages[bad]}")
            return
        for page in pages:
            rc = self._refcount.get(page)
            if rc is None:
                raise ValueError(f"double free of page {page}")
            if rc > 1:
                self._refcount[page] = rc - 1
                continue
            del self._refcount[page]
            if page in self._page_meta:
                self._reclaimable[page] = None
                self._reclaimable.move_to_end(page)
            else:
                self._free.append(page)

    # -- prefix cache ------------------------------------------------------

    def register(
        self,
        page: int,
        seq_hash: int,
        parent_hash: Optional[int],
        tokens: tuple[int, ...],
    ) -> bool:
        """Content-address a *full* page so future requests can share it.
        True when the page carries a registration afterwards (now or from
        before), False when its content is already cached under another
        page (two seqs computed the same block concurrently; the existing
        registration is kept, and a caller may offer this page again)."""
        if page in self._page_meta:
            # in step with the native pool's own table: it evicts only
            # inside allocate / clear_cache, which drain at once
            return True
        if self._np is not None:
            if not self._nlib.dyn_pool_register(
                self._np, page, seq_hash & 0xFFFFFFFFFFFFFFFF
            ):
                return False
        else:
            prev = self._by_hash.get(seq_hash)
            if prev is not None and prev != page:
                return False
            self._by_hash[seq_hash] = page
        self._page_meta[page] = (seq_hash, parent_hash, tokens)
        self.stats.stored_blocks += 1
        self._emit(
            KvEvent(
                kind="stored",
                block_hashes=(seq_hash,),
                parent_hash=parent_hash,
                token_blocks=(tokens,),
            )
        )
        return True

    def lookup(self, seq_hashes: Sequence[int]) -> list[int]:
        """Longest cached prefix: page ids for leading hashes present.

        Acquires a reference on each returned page. With state slots
        (a model with state-space layers) there is never a hit: counted
        in `match_length`, which every admission asks first.
        """
        if self.state_slots:
            return []
        if self._np is not None:
            n = len(seq_hashes)
            pages: list[int] = []
            if n:
                harr = (ctypes.c_uint64 * n)(
                    *(h & 0xFFFFFFFFFFFFFFFF for h in seq_hashes)
                )
                out = (ctypes.c_uint32 * n)()
                k = self._nlib.dyn_pool_lookup(self._np, harr, n, out)
                pages = list(out[:k])
        else:
            pages = []
            for h in seq_hashes:
                page = self._by_hash.get(h)
                if page is None:
                    break
                self._acquire(page)
                pages.append(page)
        self.stats.queries += 1
        self.stats.query_tokens += len(seq_hashes) * self.page_size
        self.stats.hit_tokens += len(pages) * self.page_size
        return pages

    def resident_match_length(self, seq_hashes: Sequence[int]) -> int:
        """Alias of match_length on the base allocator; the tiered
        subclass extends the chain through its host/disk tiers."""
        return self.match_length(seq_hashes)

    def register_promoted(
        self,
        page: int,
        seq_hash: int,
        parent_hash: Optional[int],
        tokens: tuple[int, ...],
    ) -> None:
        """Register a block whose bytes were just brought (back) onto the
        device — from a lower tier or a peer. The tiered subclass also
        drops lower-tier copies and counts the onboard."""
        self.register(page, seq_hash, parent_hash, tokens)

    def match_length(self, seq_hashes: Sequence[int]) -> int:
        """Cached-prefix length in blocks, without acquiring references.
        With state slots: 0, and a hit that would have been is counted
        (`prefix_hits_refused_state`)."""
        if self.state_slots:
            if self._match_length(seq_hashes):
                self.prefix_hits_refused_state += 1
            return 0
        return self._match_length(seq_hashes)

    def _match_length(self, seq_hashes: Sequence[int]) -> int:
        if self._np is not None:
            n = len(seq_hashes)
            if not n:
                return 0
            harr = (ctypes.c_uint64 * n)(
                *(h & 0xFFFFFFFFFFFFFFFF for h in seq_hashes)
            )
            return self._nlib.dyn_pool_match_length(self._np, harr, n)
        n = 0
        for h in seq_hashes:
            if h not in self._by_hash:
                break
            n += 1
        return n

    # -- internals ---------------------------------------------------------

    def _acquire(self, page: int) -> None:
        rc = self._refcount.get(page, 0)
        if rc == 0:
            self._reclaimable.pop(page, None)
        self._refcount[page] = rc + 1

    def _pre_evict(self, page: int) -> None:
        """Hook: called while the page's metadata (and device bytes) are
        still intact, before the registration is dropped. KVBM offload
        lives here (kvbm/manager.py)."""

    def _evict(self, page: int) -> None:
        """Python-path eviction (native evictions arrive via _drain_evicted)."""
        self._pre_evict(page)
        seq_hash, _, _ = self._page_meta.pop(page)
        del self._by_hash[seq_hash]
        self.stats.evicted_blocks += 1
        self._emit(KvEvent(kind="removed", block_hashes=(seq_hash,)))

    def _drain_evicted(self) -> None:
        """Process evictions queued inside the native pool: run the offload
        hook (device bytes are untouched until the engine's next dispatch),
        drop metadata, emit 'removed' events."""
        pending = self._nlib.dyn_pool_evicted_pending(self._np)
        if not pending:
            return
        pages = (ctypes.c_uint32 * pending)()
        hashes = (ctypes.c_uint64 * pending)()
        got = self._nlib.dyn_pool_drain_evicted(self._np, pages, hashes, pending)
        for i in range(got):
            page = pages[i]
            self._pre_evict(page)
            seq_hash, _, _ = self._page_meta.pop(page)
            self.stats.evicted_blocks += 1
            self._emit(KvEvent(kind="removed", block_hashes=(seq_hash,)))

    def _emit(self, event: KvEvent) -> None:
        if self._on_event is not None:
            self._on_event(event)

    def flush_offloads(self) -> int:
        """Tiered subclass hook: complete in-flight async offloads. The
        base pool has none."""
        return 0

    def clear_cache(self) -> int:
        """Drop all reclaimable cached pages (frontend /clear_kv_blocks)."""
        if self._np is not None:
            n = self._nlib.dyn_pool_clear_cache(self._np)
            self._drain_evicted()
            return n
        n = 0
        while self._reclaimable:
            page, _ = self._reclaimable.popitem(last=False)
            self._evict(page)
            self._free.append(page)
            n += 1
        return n
