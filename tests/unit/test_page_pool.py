"""Page-pool allocator + prefix cache unit tests (serve/cache_manager):
alloc/free/pin/COW semantics, exhaustion, LRU eviction, and no-leak
accounting across the engine's cancel/TTL/shutdown paths."""
from __future__ import annotations

import pytest

from skypilot_tpu.serve import cache_manager


class TestPagePool:

    def test_alloc_free_roundtrip(self):
        pool = cache_manager.PagePool(n_pages=8, page_size=4)
        assert pool.capacity == 7           # page 0 reserved (null)
        pages = pool.alloc(3)
        assert len(pages) == 3
        assert cache_manager.NULL_PAGE not in pages
        assert pool.used_count == 3 and pool.free_count == 4
        pool.decref(pages)
        assert pool.used_count == 0 and pool.free_count == 7

    def test_exhaustion_raises_and_is_all_or_nothing(self):
        pool = cache_manager.PagePool(n_pages=4, page_size=4)
        pool.alloc(2)
        with pytest.raises(cache_manager.PagesExhausted):
            pool.alloc(2)                   # only 1 free
        # The failed alloc must not have consumed the last page.
        assert pool.free_count == 1

    def test_refcount_sharing(self):
        pool = cache_manager.PagePool(n_pages=8, page_size=4)
        (page,) = pool.alloc(1)
        pool.incref([page])                 # a second slot adopts it
        pool.decref([page])
        assert pool.used_count == 1         # still held by one slot
        pool.decref([page])
        assert pool.used_count == 0

    def test_pin_keeps_page_resident_at_ref_zero(self):
        pool = cache_manager.PagePool(n_pages=4, page_size=4)
        (page,) = pool.alloc(1)
        pool.pin(page)
        pool.decref([page])
        assert pool.used_count == 1 and pool.pinned_count == 1
        pool.unpin(page)
        assert pool.used_count == 0 and pool.pinned_count == 0

    def test_cow_private_page_is_in_place(self):
        pool = cache_manager.PagePool(n_pages=8, page_size=4)
        (page,) = pool.alloc(1)
        writable, needs_copy = pool.cow(page)
        assert writable == page and needs_copy is False

    def test_cow_shared_page_allocates_fresh(self):
        pool = cache_manager.PagePool(n_pages=8, page_size=4)
        (page,) = pool.alloc(1)
        pool.incref([page])                 # shared by two holders
        writable, needs_copy = pool.cow(page)
        assert needs_copy is True and writable != page
        assert pool.refcount(page) == 1     # shared ref dropped
        assert pool.refcount(writable) == 1

    def test_double_free_and_bad_ops_rejected(self):
        pool = cache_manager.PagePool(n_pages=4, page_size=4)
        (page,) = pool.alloc(1)
        pool.decref([page])
        with pytest.raises(ValueError):
            pool.decref([page])
        with pytest.raises(ValueError):
            pool.pin(page)                  # unallocated
        with pytest.raises(ValueError):
            pool.unpin(page)

    def test_validation(self):
        with pytest.raises(ValueError):
            cache_manager.PagePool(n_pages=1, page_size=4)
        with pytest.raises(ValueError):
            cache_manager.PagePool(n_pages=8, page_size=0)


class TestChunkHashes:

    def test_full_pages_only_and_chain_property(self):
        h1 = cache_manager.chunk_hashes([1, 2, 3, 4, 5, 6, 7], 4)
        assert len(h1) == 1                 # one full page of 4
        h2 = cache_manager.chunk_hashes([1, 2, 3, 4, 9, 9, 9, 9], 4)
        assert h2[0] == h1[0]               # same first page
        # The chain: page 2 differs if page 1 differed.
        a = cache_manager.chunk_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
        b = cache_manager.chunk_hashes([9, 2, 3, 4, 5, 6, 7, 8], 4)
        assert a[1] != b[1]

    def test_short_prompt_no_pages(self):
        assert cache_manager.chunk_hashes([1, 2, 3], 4) == []


class TestPrefixCache:

    def test_match_increfs_and_counts(self):
        pool = cache_manager.PagePool(n_pages=8, page_size=2)
        cache = cache_manager.PrefixCache(pool)
        pages = pool.alloc(2)
        hashes = cache_manager.chunk_hashes([1, 2, 3, 4], 2)
        cache.register(hashes, pages)
        pool.decref(pages)                  # owner finished; pins hold
        matched = cache.match(hashes)
        assert matched == pages
        assert cache.hits == 2 and cache.misses == 0
        assert pool.refcount(pages[0]) == 1  # held for the adopter
        miss = cache.match(cache_manager.chunk_hashes([9, 9], 2))
        assert miss == [] and cache.misses == 1

    def test_partial_chain_match(self):
        pool = cache_manager.PagePool(n_pages=8, page_size=2)
        cache = cache_manager.PrefixCache(pool)
        pages = pool.alloc(2)
        cache.register(cache_manager.chunk_hashes([1, 2, 3, 4], 2),
                       pages)
        pool.decref(pages)
        # Shares page 1, diverges in page 2 (mid-prompt divergence).
        matched = cache.match(
            cache_manager.chunk_hashes([1, 2, 9, 9], 2))
        assert matched == pages[:1]
        pool.decref(matched)

    def test_lru_eviction_skips_referenced_pages(self):
        pool = cache_manager.PagePool(n_pages=8, page_size=2)
        cache = cache_manager.PrefixCache(pool)
        a = pool.alloc(1)
        b = pool.alloc(1)
        cache.register([111], a)
        cache.register([222], b)
        pool.decref(b)                      # only b is idle
        # a is oldest but still referenced -> eviction must skip it.
        released = cache.evict(1)
        assert released == 1
        assert len(cache) == 1
        assert pool.refcount(a[0]) == 1     # untouched

    def test_evictable_counts_idle_only(self):
        pool = cache_manager.PagePool(n_pages=8, page_size=2)
        cache = cache_manager.PrefixCache(pool)
        a = pool.alloc(1)
        cache.register([1], a)
        assert cache.evictable() == 0       # ref still held
        pool.decref(a)
        assert cache.evictable() == 1

    def test_headroom_probe_beside_the_worker(self):
        """submit() threads ask `can_admit` while the engine's worker
        matches prefixes (an LRU touch of every matched entry): with
        some thousands of entries the probe used to walk the entries
        and die of 'OrderedDict mutated during iteration'."""
        import threading
        mgr = cache_manager.PagedKVManager(4097, 16, slots=4)
        pages = mgr.pool.alloc(4000)
        mgr.prefix.register(list(range(4000)), pages)
        mgr.pool.decref(pages)
        errors, stop = [], threading.Event()

        def probe():
            while not stop.is_set():
                try:
                    # 96 free + 4000 cached, 512 of them matched.
                    assert mgr.can_admit(3000)
                except Exception as e:  # pylint: disable=broad-except
                    errors.append(repr(e))
                    return

        prober = threading.Thread(target=probe)
        prober.start()
        try:
            for _ in range(300):
                mgr.pool.decref(mgr.prefix.match(list(range(512))))
                if errors:
                    break
        finally:
            stop.set()
            prober.join()
        assert not errors



class TestPagedKVManager:

    def test_pages_needed(self):
        mgr = cache_manager.PagedKVManager(16, 4, slots=2)
        # prompt 5 + 4 new: positions 0..7 -> 2 pages of 4.
        assert mgr.pages_needed(5, 4) == 2
        assert mgr.pages_needed(1, 1) == 1
        assert mgr.pages_needed(4, 5) == 2

    def test_plan_commit_release_no_leak(self):
        mgr = cache_manager.PagedKVManager(16, 4, slots=2)
        plan = mgr.plan_admission(list(range(10)), 4)
        assert len(plan.row) == mgr.pages_needed(10, 4)
        mgr.commit(0, plan)
        assert mgr.pool.used_count == len(plan.row)
        mgr.release(0)
        assert mgr.pool.used_count == 0
        mgr.release(0)                      # idempotent

    def test_exhaustion_releases_matched_pages(self):
        mgr = cache_manager.PagedKVManager(6, 2, slots=2)  # 5 usable
        plan = mgr.plan_admission([1, 2, 3, 4, 5], 2)      # 3 pages
        mgr.commit(0, plan)
        mgr.register_prefix(plan)
        mgr.release(0)                      # pages pinned, not leaked
        used_before = mgr.pool.used_count
        # Same prefix matches 2 pages, but the fresh remainder cannot
        # fit -> the matched refs must be released on failure.
        with pytest.raises(cache_manager.PagesExhausted):
            mgr.plan_admission([1, 2, 3, 4, 5] + [7] * 6, 2)
        assert mgr.pool.used_count == used_before
        for page in plan.row[:2]:
            assert mgr.pool.refcount(page) == 0

    def test_eviction_under_pressure(self):
        mgr = cache_manager.PagedKVManager(6, 2, slots=2)   # 5 usable
        plan = mgr.plan_admission([1, 2, 3, 4], 2)          # 3 pages
        mgr.commit(0, plan)
        mgr.register_prefix(plan)           # 1 full page pinned
        mgr.release(0)
        assert mgr.pool.free_count == 4     # 1 held by the pin
        # A 5-page request forces the prefix entry out.
        plan2 = mgr.plan_admission([9] * 8, 3, prefix_ok=False)
        assert len(plan2.row) == 5
        mgr.commit(1, plan2)
        mgr.release(1)
        assert mgr.pool.used_count == 0

    def test_release_all_clears_pins(self):
        mgr = cache_manager.PagedKVManager(16, 2, slots=2)
        plan = mgr.plan_admission([1, 2, 3, 4, 5], 2)
        mgr.commit(0, plan)
        mgr.register_prefix(plan)
        mgr.release_all()
        assert mgr.pool.used_count == 0
        assert mgr.pool.pinned_count == 0

    def test_stats_shape(self):
        mgr = cache_manager.PagedKVManager(8, 4, slots=2)
        stats = mgr.stats()
        for key in ('kv_pages_total', 'kv_pages_used', 'kv_pages_free',
                    'kv_pages_pinned', 'page_size',
                    'prefix_cache_entries', 'prefix_cache_hits',
                    'prefix_cache_misses'):
            assert key in stats
        assert stats['kv_pages_total'] == 7
