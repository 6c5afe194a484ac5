"""Paged KV-cache management for the batching engine (host side).

vLLM-style block pooling rebuilt TPU-first: the serving KV cache is a
FIXED pool of fixed-size pages (`models/decode.py` holds the device
arrays `[L, n_pages, h_kv, page_size, d]`); this module owns every
host-side decision about those pages:

- :class:`PagePool` — the allocator.  Free list + per-page reference
  counts + pin counts; page 0 is reserved as the NULL page (freed
  slots' block tables point at it, so a stale device-side write after
  a slot is recycled can only scribble on garbage no request reads).
  Exhaustion raises :class:`PagesExhausted` — the engine turns that
  into admission backpressure (HTTP 429 + Retry-After), never an
  engine crash.  The ``serve.page_pool`` chaos site lives on the
  allocation path (deny -> exhaustion, delay -> slowed admission).
- :class:`PrefixCache` — content-addressed reuse.  Every FULL page of
  a prompt's prefilled region is registered under a chain hash
  (hash of the page's tokens and every page before it), so a request
  sharing a system prompt adopts the cached pages instead of
  re-prefilling them; entries are LRU-evicted under pool pressure.
  Only full pages are shared and shared pages are never written (the
  write cursor always lands in a privately-owned page), so sessions
  that diverge MID-page simply stop matching at that page — each gets
  its own divergence page.  :meth:`PagePool.cow` is the escape hatch
  should a writer ever hold a shared page (copy, drop the shared ref).
- :class:`PagedKVManager` — what the engine talks to: plan an
  admission (prefix match + allocation + block-table row), track which
  slot owns which pages, and release them on completion/cancel/TTL so
  the pool can never leak.

Why pages: a dense per-slot cache reserves `max_len` positions per
slot, so replica concurrency is bounded by the WORST-CASE sequence
length.  Pages bound memory by the ACTUAL tokens a request can touch
(`ceil((prompt + max_new - 1) / page_size)`), decoupling slot count
from max_len — the difference between tens and thousands of sessions
per replica at fixed HBM.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

from skypilot_tpu import sky_logging
from skypilot_tpu.observability import metrics as metrics_lib

logger = sky_logging.init_logger(__name__)

# The reserved null page: never allocated, the block-table target of
# freed/empty slots (stale device writes land here harmlessly).
NULL_PAGE = 0

# Process-global instruments (Prometheus registry -> GET /metrics).
_M_PAGES_TOTAL = metrics_lib.gauge(
    'skytpu_engine_kv_pages_total',
    'Allocatable KV pages in the page pool (excludes the null page).')
_M_PAGES_USED = metrics_lib.gauge(
    'skytpu_engine_kv_pages_used',
    'KV pages currently referenced by live slots or the prefix cache.')
_M_PAGES_PINNED = metrics_lib.gauge(
    'skytpu_engine_kv_pages_pinned',
    'KV pages pinned by the prefix cache (reusable cached prefixes).')
_M_PREFIX_HITS = metrics_lib.counter(
    'skytpu_engine_prefix_cache_hits_total',
    'Prompt pages served from the prefix cache instead of prefill.')
_M_PREFIX_MISSES = metrics_lib.counter(
    'skytpu_engine_prefix_cache_misses_total',
    'Prompt pages that had to be prefilled (no cached prefix).')


class PagesExhausted(RuntimeError):
    """The page pool cannot satisfy an allocation right now.

    The engine maps this to admission backpressure: the request stays
    queued (or the submit gets HTTP 429 + Retry-After) until pages
    free — a full pool must degrade to honest rejections, never an
    engine failure.
    """


def chunk_hashes(token_ids: Sequence[int], page_size: int) -> List[int]:
    """Chain hashes of every FULL page of `token_ids`.

    hash(page j) covers pages 0..j (the chain), so a hit at page j
    certifies the whole prefix — two prompts can only share page j if
    every earlier token matches too.
    """
    out: List[int] = []
    prev = 0
    for start in range(0, len(token_ids) - page_size + 1, page_size):
        prev = hash((prev, tuple(token_ids[start:start + page_size])))
        out.append(prev)
    return out


class PagePool:
    """Fixed pool of KV pages: free list + refcounts + pins.

    A page is USED while `ref + pin > 0`; it returns to the free list
    when both hit zero.  Slots hold refs; the prefix cache holds pins.
    Thread-safe: submit() threads probe headroom while the engine
    worker allocates/frees.
    """

    def __init__(self, n_pages: int, page_size: int,
                 journal: Optional[Any] = None) -> None:
        if n_pages < 2:
            raise ValueError(f'page pool needs >= 2 pages (one is the '
                             f'reserved null page), got {n_pages}')
        if page_size < 1:
            raise ValueError(f'page_size must be >= 1, got {page_size}')
        self.n_pages = n_pages
        self.page_size = page_size
        self._lock = threading.Lock()
        # Page NULL_PAGE is reserved; everything else starts free.
        self._free: collections.deque = collections.deque(
            range(1, n_pages))
        self._ref = [0] * n_pages
        self._pin = [0] * n_pages
        # Chaos scenarios replay this journal to prove alloc/free
        # balance; None in production (no I/O on the admission path).
        self._journal = journal
        _M_PAGES_TOTAL.set(self.capacity)
        _M_PAGES_USED.set(0)
        _M_PAGES_PINNED.set(0)

    # ------------------------------------------------------------- views

    @property
    def capacity(self) -> int:
        return self.n_pages - 1          # null page excluded

    @property
    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_count(self) -> int:
        with self._lock:
            return self.capacity - len(self._free)

    @property
    def pinned_count(self) -> int:
        with self._lock:
            return sum(1 for p in self._pin if p > 0)

    @property
    def idle_pinned_count(self) -> int:
        """Pages that only the prefix cache holds (pinned, no slot
        ref): what evicting its entries would free."""
        with self._lock:
            return sum(1 for ref, pin in zip(self._ref, self._pin)
                       if pin > 0 and ref == 0)

    def refcount(self, page: int) -> int:
        with self._lock:
            return self._ref[page]

    # --------------------------------------------------------- lifecycle

    def alloc(self, n: int) -> List[int]:
        """Allocate n fresh pages (ref=1 each); raises PagesExhausted.

        All-or-nothing: a partial admission would strand a half-built
        block table holding pages no tick will ever use.
        """
        from skypilot_tpu.chaos import injector  # pylint: disable=import-outside-toplevel
        if injector.inject('serve.page_pool', need=n,
                           free=self.free_count) is injector.DENY:
            raise PagesExhausted(
                f'chaos: page pool denied allocation of {n} page(s)')
        with self._lock:
            if n > len(self._free):
                raise PagesExhausted(
                    f'page pool exhausted: need {n} page(s), '
                    f'{len(self._free)} free of {self.capacity}')
            pages = [self._free.popleft() for _ in range(n)]
            for p in pages:
                self._ref[p] = 1
        self._record('kv_pages_alloc', pages)
        self._set_gauges()
        return pages

    def incref(self, pages: Sequence[int]) -> None:
        with self._lock:
            for p in pages:
                if self._ref[p] + self._pin[p] <= 0:
                    raise ValueError(f'incref of unallocated page {p}')
                self._ref[p] += 1

    def decref(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; pages with no refs and no pins
        return to the free list."""
        freed: List[int] = []
        with self._lock:
            for p in pages:
                if self._ref[p] <= 0:
                    raise ValueError(f'decref of page {p} with refcount '
                                     f'{self._ref[p]}')
                self._ref[p] -= 1
                if self._ref[p] == 0 and self._pin[p] == 0:
                    self._free.append(p)
                    freed.append(p)
        if freed:
            self._record('kv_pages_free', freed)
        self._set_gauges()

    def pin(self, page: int) -> None:
        """Prefix-cache hold: keeps the page resident at ref 0."""
        with self._lock:
            if self._ref[page] + self._pin[page] <= 0:
                raise ValueError(f'pin of unallocated page {page}')
            self._pin[page] += 1
        self._set_gauges()

    def unpin(self, page: int) -> None:
        freed = False
        with self._lock:
            if self._pin[page] <= 0:
                raise ValueError(f'unpin of unpinned page {page}')
            self._pin[page] -= 1
            if self._pin[page] == 0 and self._ref[page] == 0:
                self._free.append(page)
                freed = True
        if freed:
            self._record('kv_pages_free', [page])
        self._set_gauges()

    def cow(self, page: int) -> Tuple[int, bool]:
        """Copy-on-write: make `page` safe to mutate for ONE holder.

        Returns (writable_page, needs_copy).  A page with a single
        reference and no pins is already private — returned as-is.  A
        shared/pinned page gets a fresh page allocated (the caller must
        copy the device contents) and the shared reference dropped.
        """
        with self._lock:
            if self._ref[page] == 1 and self._pin[page] == 0:
                return page, False
        fresh = self.alloc(1)[0]
        self.decref([page])
        return fresh, True

    # ----------------------------------------------------------- plumbing

    def _set_gauges(self) -> None:
        with self._lock:
            used = self.capacity - len(self._free)
            pinned = sum(1 for p in self._pin if p > 0)
        _M_PAGES_USED.set(used)
        _M_PAGES_PINNED.set(pinned)

    def _record(self, event: str, pages: List[int]) -> None:
        if self._journal is None:
            return
        try:
            self._journal.append(event, pages=list(pages), n=len(pages))
        except Exception:  # pylint: disable=broad-except
            pass  # recording must never break the admission path


class PrefixCache:
    """Chain-hash -> cached page, LRU-evicted under pool pressure.

    Entries pin their page in the pool; a match increfs the page for
    the adopting slot (the entry itself stays, so a third request hits
    too).  Only FULL prompt pages are ever registered, and full pages
    are immutable once written — matched pages are read-only by
    construction.
    """

    def __init__(self, pool: PagePool) -> None:
        self._pool = pool
        # hash -> page id, in LRU order (oldest first).
        self._entries: 'collections.OrderedDict[int, int]' = (
            collections.OrderedDict())
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def contains(self, h: int) -> bool:
        """Pure membership probe (no incref, no LRU touch) — the KV
        handoff import uses it to skip pages already resident."""
        return h in self._entries

    def match(self, hashes: Sequence[int]) -> List[int]:
        """Longest chain of cached pages for these chain hashes; the
        matched pages are incref'd for the caller (one ref per page)."""
        pages: List[int] = []
        for h in hashes:
            page = self._entries.get(h)
            if page is None:
                break
            pages.append(page)
            self._entries.move_to_end(h)   # LRU touch
        if pages:
            self._pool.incref(pages)
        self.hits += len(pages)
        self.misses += len(hashes) - len(pages)
        _M_PREFIX_HITS.inc(len(pages))
        _M_PREFIX_MISSES.inc(len(hashes) - len(pages))
        return pages

    def register(self, hashes: Sequence[int],
                 pages: Sequence[int]) -> None:
        """Publish freshly prefilled full pages (hashes[i] names
        pages[i]); duplicates keep the existing entry (first writer
        wins — both copies are identical by construction)."""
        for h, page in zip(hashes, pages):
            if h in self._entries:
                self._entries.move_to_end(h)
                continue
            self._pool.pin(page)
            self._entries[h] = page

    def evict(self, n_pages: int) -> int:
        """Unpin up to n_pages LRU entries whose pages are idle (no
        slot refs — unpinning those actually frees pages); returns how
        many pages were released to the pool."""
        released = 0
        for h in list(self._entries):
            if released >= n_pages:
                break
            page = self._entries[h]
            if self._pool.refcount(page) > 0:
                continue  # a live slot still reads it; keep the entry
            del self._entries[h]
            self._pool.unpin(page)
            released += 1
        return released

    def evictable(self) -> int:
        """Pages the cache could release right now (no slot refs).
        Each entry pins its own page once, so the pool can say, under
        its lock: submit() threads probe this (`can_admit`) while the
        worker registers, matches (an LRU touch) and evicts entries,
        and the entries themselves are the worker's alone to walk."""
        return self._pool.idle_pinned_count

    def hot_entries(self, n: int) -> List[Tuple[int, int]]:
        """The n most-recently-used (hash, page) entries.  Entries are
        independent hash->page mappings (a chain lookup walks its own
        hashes), so any subset transfers cleanly.  Drain-time handoff
        exports these to a surviving sibling so a retirement does not
        cold-start every pinned session."""
        items = list(self._entries.items())
        return items[-n:] if n > 0 else []

    def clear(self) -> None:
        for h in list(self._entries):
            page = self._entries.pop(h)
            self._pool.unpin(page)


@dataclasses.dataclass
class AdmissionPlan:
    """Everything the engine needs to land one request in pages."""
    row: List[int]            # block-table row: reused + fresh pages
    reuse_pages: List[int]    # cached pages adopted (prefix hit)
    fresh_pages: List[int]    # newly allocated pages
    n_reuse_tokens: int       # positions [0, n_reuse_tokens) are cached
    page_hashes: List[int]    # chain hashes of the prompt's full pages

    @property
    def prefix_hit_pages(self) -> int:
        return len(self.reuse_pages)


class PagedKVManager:
    """Host-side paged-KV orchestration for one engine.

    Owns the pool + prefix cache + the slot->pages ownership map; the
    engine calls `plan_admission` when a slot frees, `register_prefix`
    when the prompt's pages are fully written, and `release` on every
    completion/cancel/expiry path.
    """

    def __init__(self, n_pages: int, page_size: int, slots: int,
                 prefix_caching: bool = True,
                 journal: Optional[Any] = None) -> None:
        self.pool = PagePool(n_pages, page_size, journal=journal)
        self.page_size = page_size
        self.prefix_caching = prefix_caching
        self.prefix = PrefixCache(self.pool)
        self._slot_pages: Dict[int, List[int]] = {}
        del slots  # sized by the engine's device arrays, not here

    # ------------------------------------------------------------ sizing

    @staticmethod
    def pool_pages(kv_pages: Optional[int], slots: int, max_len: int,
                   page_size: int) -> int:
        """The pool's `n_pages` for an engine's geometry: `kv_pages`
        as given, else what lets every slot hold a request of `max_len`
        at once (`slots * max_len / page_size` pages to hand out, and
        the reserved null page)."""
        if max_len % page_size:
            raise ValueError(
                f'max_len {max_len} must be a multiple of '
                f'page_size {page_size} (private prefill caches '
                f'scatter whole pages into the pool)')
        if kv_pages is None:
            return slots * (max_len // page_size) + 1
        return int(kv_pages)

    def pages_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        """Pages covering every position this request can touch: the
        prompt occupies [0, n) and decode writes through position
        n + max_new - 2 (the n-1/last-token trick folds the last prompt
        token into the first decode write)."""
        total_positions = max(1, prompt_len + max_new_tokens - 1)
        return -(-total_positions // self.page_size)

    def can_admit(self, n_pages: int) -> bool:
        """Could an allocation of n_pages succeed right now (counting
        prefix entries that eviction could release)?"""
        return (self.pool.free_count + self.prefix.evictable()
                >= n_pages)

    # --------------------------------------------------------- admission

    def plan_admission(self, prompt_ids: Sequence[int],
                       max_new_tokens: int, *,
                       prefix_ok: bool = True) -> AdmissionPlan:
        """Match the prompt against the prefix cache and allocate the
        fresh remainder; raises PagesExhausted (with any matched pages
        released) when the pool cannot cover it."""
        ps = self.page_size
        n = len(prompt_ids)
        total_pages = self.pages_needed(n, max_new_tokens)
        # Only pages fully inside the PREFILLED region [0, n-1) are
        # shareable (position n-1 onward is written during decode).
        hashes = (chunk_hashes(prompt_ids[:n - 1], ps)
                  if (prefix_ok and self.prefix_caching and n > 1)
                  else [])
        reuse = self.prefix.match(hashes)
        fresh_needed = total_pages - len(reuse)
        try:
            fresh = self._alloc_with_eviction(fresh_needed)
        except PagesExhausted:
            if reuse:
                self.pool.decref(reuse)
            raise
        return AdmissionPlan(row=reuse + fresh, reuse_pages=reuse,
                             fresh_pages=fresh,
                             n_reuse_tokens=len(reuse) * ps,
                             page_hashes=hashes)

    def _alloc_with_eviction(self, n: int) -> List[int]:
        if n <= 0:
            return []
        shortfall = n - self.pool.free_count
        if shortfall > 0:
            self.prefix.evict(shortfall)
        return self.pool.alloc(n)

    def alloc_pages(self, n: int) -> List[int]:
        """Allocate n pages (evicting idle prefix entries under
        pressure); raises PagesExhausted.  The KV-handoff import path
        uses this to stage incoming pages before publishing them."""
        return self._alloc_with_eviction(n)

    def import_prefix_depth(self, hashes: Sequence[int]) -> int:
        """Longest leading run of `hashes` already resident in the
        prefix cache — an import skips those pages (the chain property
        means a later hash can only be cached if every earlier one
        was; stop at the first miss)."""
        depth = 0
        for h in hashes:
            if not self.prefix.contains(h):
                break
            depth += 1
        return depth

    def commit(self, slot: int, plan: AdmissionPlan) -> None:
        """Record slot ownership (release() undoes it)."""
        self._slot_pages[slot] = list(plan.row)

    def slot_row(self, slot: int) -> Optional[List[int]]:
        """The page row a slot currently owns (None before commit) —
        what a slice replica's rank 0 broadcasts so follower ranks can
        mirror the block-table admission without re-planning."""
        pages = self._slot_pages.get(slot)
        return list(pages) if pages is not None else None

    def abandon(self, plan: AdmissionPlan) -> None:
        """Drop a plan that never reached a slot (cancelled mid-
        prefill before commit, admission error)."""
        if plan.row:
            self.pool.decref(plan.row)

    def register_prefix(self, plan: AdmissionPlan) -> None:
        """Publish the plan's freshly-written FULL pages for reuse.
        Safe to call once the prompt's pages hold final content (at
        activation: every position < n-1 has been written)."""
        if not self.prefix_caching:
            return
        full = len(plan.page_hashes)       # full pages in [0, n-1)
        r = len(plan.reuse_pages)
        if full <= r:
            return
        self.prefix.register(plan.page_hashes[r:full],
                             plan.row[r:full])

    def release(self, slot: int) -> None:
        """Free a slot's pages (completion, cancel, TTL, shutdown);
        idempotent — release of a slot with no pages is a no-op."""
        pages = self._slot_pages.pop(slot, None)
        if pages:
            self.pool.decref(pages)

    def release_all(self) -> None:
        for slot in list(self._slot_pages):
            self.release(slot)
        self.prefix.clear()

    # ------------------------------------------------------------- stats

    def stats(self) -> Dict[str, Any]:
        stats = {
            'kv_pages_total': self.pool.capacity,
            'kv_pages_used': self.pool.used_count,
            'kv_pages_free': self.pool.free_count,
            'kv_pages_pinned': self.pool.pinned_count,
            'page_size': self.page_size,
            'prefix_cache_entries': len(self.prefix),
            'prefix_cache_hits': self.prefix.hits,
            'prefix_cache_misses': self.prefix.misses,
        }
        _M_PAGES_TOTAL.set(stats['kv_pages_total'])
        _M_PAGES_USED.set(stats['kv_pages_used'])
        _M_PAGES_PINNED.set(stats['kv_pages_pinned'])
        return stats
