"""Page-granular prefix KV cache for the LLM engine.

vLLM-style automatic prefix caching for the paged engine
(serve/llm.py): prompts are chopped into fixed-size token blocks, each
block is identified by a CHAIN hash (its own tokens + the parent
block's digest, so a digest names an entire prefix, not just 64 loose
tokens), and every full block a request prefills stays where prefill
wrote it — a page of the engine's device pool, sealed under its digest
in a per-engine refcounted allocator. The next request sharing that
prefix points its page table at the matched pages (a refcount bump, no
copy) and prefills only the uncached tail (gpt2_decode.prefill_paged) —
TTFT stops paying for the shared system prompt.

Lifecycle contract: ``alloc`` and ``match_pages`` both leave the caller
holding ONE ref per returned page; the engine releases them when the
request leaves it (finish/cancel/fail/unload). Only refcount-0 sealed
pages are LRU-evictable; ``close()`` drops everything regardless of
refcounts — a multiplex eviction must not strand resident pages (the
pool is gone with the engine).

Kill switch: RT_SERVE_PREFIX_CACHE=0 (checked at admission).
"""

from __future__ import annotations

import hashlib
import heapq
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu.observability import core_metrics
from ray_tpu.utils.config import config

# Live pools in this process (engine model_id -> pool), for unload
# accounting and tests. An engine owns one pool.
_POOLS: Dict[int, Any] = {}
_POOLS_LOCK = threading.Lock()


def hash_blocks(tokens: Sequence[int], block_tokens: int) -> List[str]:
    """Chained content digests of the prompt's FULL blocks.

    digest_i = blake2b(digest_{i-1} || int32 tokens of block i), so two
    prompts share digest_i iff they share the entire first (i+1) blocks
    — a pool lookup never has to compare token lists, and the digests
    are stable across processes/replicas (pure content, no pid/seed).
    The trailing partial block is never hashed: only full blocks are
    cacheable."""
    n_full = len(tokens) // block_tokens
    if n_full <= 0:
        return []
    arr = np.asarray(tokens[: n_full * block_tokens], dtype=np.int32)
    out: List[str] = []
    parent = b""
    for i in range(n_full):
        h = hashlib.blake2b(digest_size=16)
        h.update(parent)
        h.update(arr[i * block_tokens : (i + 1) * block_tokens].tobytes())
        parent = h.digest()
        out.append(parent.hex())
    return out


class _Page:
    """Metadata for one device-resident KV page. The page's K/V content
    lives in the engine's paged device cache (page ``idx`` of the pools
    ``gpt2_decode.init_paged_cache`` returns, in the shape that function
    stores them in); the pool only tracks who may read it."""

    __slots__ = ("idx", "refs", "digest", "tick")

    def __init__(self, idx: int):
        self.idx = idx
        self.refs = 0
        # set when the page is SEALED as a prefix block: its content is
        # the KV of a full prompt block named by this chain digest
        self.digest: Optional[str] = None
        self.tick = 0


class PagedKVPool:
    """Refcounted allocator over ONE device-resident page pool shared by
    generation KV and prefix KV (vLLM-style paged attention, metadata
    side). It holds NO host tensor copies — a prefix hit is a refcount
    bump on pages already sitting in the device cache, zero block
    copies.

    Page 0 is a reserved scratch page, never allocated: inactive decode
    rows scatter their junk K/V there (their page tables are all-zero),
    so the jitted decode step needs no per-row validity branch.

    Lifecycle: ``alloc`` returns pages with one ref each (the admitting
    request's pin). ``seal`` registers a written page under its chain
    digest so later ``match_pages`` calls can pin it too (one more ref
    per reader). ``release_pages`` drops refs; a ref-0 UNSEALED page
    goes straight back to the free list, a ref-0 sealed page stays
    resident as cache and is reclaimed by global LRU only when ``alloc``
    runs dry — that residency IS the prefix cache, and eviction order is
    strictly least-recently-matched over everything not pinned by a
    live request: the victim is the ref-0 sealed page of the lowest
    ``tick``, and a page's tick is written where it is allocated, sealed
    or matched, never where it is released.

    The evictable pages are kept in that order (``_evictable``: a heap of
    ``(tick, idx)``, pushed where a sealed page's refs reach 0), so one
    eviction is O(log n) and ``alloc`` O(pages asked), not a scan of
    every sealed page a page evicted. A page matched, sealed anew or
    evicted since its entry was pushed has another tick, pins or no
    digest: the entry is stale and dropped where it is popped; the heap
    is rebuilt from the live entries once the stale ones outnumber
    them, so it stays O(sealed pages)."""

    def __init__(self, model_id: str, num_pages: int,
                 page_tokens: Optional[int] = None):
        self.model_id = model_id
        self.page_tokens = int(
            page_tokens or config.serve_prefix_block_tokens
        )
        self.num_pages = int(num_pages)
        if self.num_pages < 2:
            raise ValueError("paged pool needs >= 2 pages (page 0 is scratch)")
        self._lock = threading.Lock()
        self._pages: List[_Page] = [_Page(i) for i in range(self.num_pages)]
        # page 0 reserved as scratch: never on the free list
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._sealed: Dict[str, int] = {}  # digest -> page idx
        # (tick, idx) of the sealed pages whose refs reached 0, stale
        # entries among them (see the class's word on the order)
        self._evictable: List[Tuple[int, int]] = []
        self._tick = 0
        self._closed = False
        # plain counters independent of the metrics kill switch, for
        # engine stats()/bench/test assertions
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        with _POOLS_LOCK:
            _POOLS[id(self)] = self

    # -- allocation ----------------------------------------------------

    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages (refs=1 each), evicting least-recently-
        used ref-0 sealed pages if the free list runs dry. Returns None
        — and takes nothing — when even eviction can't cover the ask:
        admission defers, it never half-allocates."""
        if n <= 0:
            return []
        with self._lock:
            if self._closed:
                return None
            while len(self._free) < n and self._evict_one_locked():
                pass
            if len(self._free) < n:
                return None
            out = [self._free.pop() for _ in range(n)]
            for idx in out:
                pg = self._pages[idx]
                pg.refs = 1
                pg.digest = None
                self._tick += 1
                pg.tick = self._tick
            return out

    def _evict_one_locked(self) -> bool:
        heap = self._evictable
        while heap:
            tick, idx = heapq.heappop(heap)
            victim = self._pages[idx]
            if not self._still_evictable(victim, tick):
                continue
            del self._sealed[victim.digest]
            victim.digest = None
            self._free.append(idx)
            self.evictions += 1
            return True
        return False  # every sealed page pinned by a live request

    @staticmethod
    def _still_evictable(pg: _Page, tick: int) -> bool:
        """Is the heap's entry ``(tick, pg.idx)`` still the page's? Not if
        it was matched, sealed anew or evicted since the entry was pushed."""
        return pg.digest is not None and not pg.refs and pg.tick == tick

    def _evictable_locked(self, pg: _Page) -> None:
        """A sealed page no request pins: it takes its place in the
        eviction order under the tick it has now."""
        heap = self._evictable
        heapq.heappush(heap, (pg.tick, pg.idx))
        if len(heap) > 2 * len(self._sealed) + 64:
            pages = self._pages
            heap[:] = [(t, i) for t, i in heap if self._still_evictable(pages[i], t)]
            heapq.heapify(heap)

    # -- prefix matching / sealing ------------------------------------

    def match_pages(
        self, digests: Sequence[str], max_tokens: int
    ) -> Tuple[List[str], List[int]]:
        """Longest resident chain prefix of ``digests`` (capped so at
        most ``max_tokens`` tokens come from cache — the engine keeps at
        least one prompt token for the tail prefill). Increfs every
        matched page; caller must release_pages(). ZERO copies: the
        returned page indices go straight into the request's page table."""
        cap = max(0, int(max_tokens)) // self.page_tokens
        held: List[str] = []
        pages: List[int] = []
        with self._lock:
            if not self._closed:
                for d in digests[:cap]:
                    idx = self._sealed.get(d)
                    if idx is None:
                        break
                    pg = self._pages[idx]
                    pg.refs += 1
                    self._tick += 1
                    pg.tick = self._tick
                    held.append(d)
                    pages.append(idx)
            hits = len(held)
            misses = len(digests) - hits
            self.hits += hits
            self.misses += misses
            if core_metrics.ENABLED:
                tags = {"deployment": self.model_id}
                if hits:
                    core_metrics.serve_prefix_cache_hits.inc(hits, tags=tags)
                if misses:
                    core_metrics.serve_prefix_cache_misses.inc(
                        misses, tags=tags
                    )
        return held, pages

    def seal(self, digest: str, page: int) -> bool:
        """Register an already-written page as the prefix block named by
        ``digest`` — no copy, the KV is already in the device cache.
        Returns False (page stays private to its request, freed on
        release) when the digest is already sealed elsewhere: two
        racing requests with the same prompt must converge on ONE
        canonical page."""
        with self._lock:
            if self._closed or digest in self._sealed:
                return False
            pg = self._pages[page]
            pg.digest = digest
            self._sealed[digest] = page
            self._tick += 1
            pg.tick = self._tick
            if pg.refs == 0:
                self._evictable_locked(pg)
            return True

    # -- release / maintenance ----------------------------------------

    def release_pages(self, pages: Sequence[int]) -> None:
        """Drop the caller's pins. Ref-0 unsealed pages return to the
        free list immediately; ref-0 sealed pages stay resident (LRU-
        evictable) — that residency is the cache."""
        if not pages:
            return
        with self._lock:
            for idx in pages:
                pg = self._pages[idx]
                pinned = pg.refs > 0
                if pinned:
                    pg.refs -= 1
                if pg.refs or self._closed:
                    continue
                if pg.digest is None:
                    self._free.append(idx)
                elif pinned:
                    self._evictable_locked(pg)

    def reset(self) -> None:
        """Drop ALL metadata (a poisoned engine round rebuilt the device
        cache with zeros, so every sealed page's content is gone)."""
        with self._lock:
            if self._closed:
                return
            for pg in self._pages:
                pg.refs = 0
                pg.digest = None
                pg.tick = 0
            self._sealed.clear()
            self._evictable.clear()
            self._free = list(range(self.num_pages - 1, 0, -1))
            self._tick = 0

    # -- introspection -------------------------------------------------

    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def resident(self) -> int:
        """Sealed prefix pages resident."""
        with self._lock:
            return len(self._sealed)

    def ref_count(self, digest: str) -> int:
        with self._lock:
            idx = self._sealed.get(digest)
            return self._pages[idx].refs if idx is not None else 0

    def page_refs(self, page: int) -> int:
        with self._lock:
            return self._pages[page].refs

    def stats(self) -> Dict[str, int]:
        with self._lock:
            free = len(self._free)
            return {
                "blocks": len(self._sealed),
                "block_tokens": self.page_tokens,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "pages_total": self.num_pages - 1,  # scratch excluded
                "pages_free": free,
                "pages_occupied": self.num_pages - 1 - free,
                "prefix_resident": len(self._sealed),
            }

    def close(self) -> None:
        """Engine unload/eviction: drop everything regardless of refs —
        outstanding pins die with the engine's sequences."""
        with self._lock:
            for pg in self._pages:
                pg.refs = 0
                pg.digest = None
            self._sealed.clear()
            self._evictable.clear()
            self._free = []
            self._closed = True
        with _POOLS_LOCK:
            _POOLS.pop(id(self), None)


def live_pools() -> List[Any]:
    """Pools not yet close()d in this process (test/debug hook)."""
    with _POOLS_LOCK:
        return list(_POOLS.values())
