"""Block-allocator and paged-cache invariants (DESIGN.md §7.1): no
double-free, ownership enforced, and no leaked pages after a full trace."""

import numpy as np
import pytest

from repro.serve.kv_cache import (
    OutOfPages,
    PageAllocator,
    PagedCacheConfig,
    PagedKVCache,
)


class TestPageAllocator:
    def test_alloc_until_exhausted(self):
        a = PageAllocator(4)
        pages = [a.alloc(owner=0) for _ in range(4)]
        assert sorted(pages) == [0, 1, 2, 3]
        assert a.n_free == 0
        with pytest.raises(OutOfPages):
            a.alloc(owner=0)

    def test_free_recycles(self):
        a = PageAllocator(2)
        p = a.alloc(owner=1)
        a.free(p, owner=1)
        assert a.n_free == 2
        assert a.alloc(owner=2) == p  # LIFO reuse

    def test_double_free_raises(self):
        a = PageAllocator(2)
        p = a.alloc(owner=0)
        a.free(p, owner=0)
        with pytest.raises(ValueError, match="double free"):
            a.free(p, owner=0)

    def test_foreign_free_raises(self):
        a = PageAllocator(2)
        p = a.alloc(owner=0)
        with pytest.raises(ValueError, match="owned by lane 0"):
            a.free(p, owner=1)

    def test_pages_of_tracks_ownership(self):
        a = PageAllocator(4)
        mine = {a.alloc(owner=7) for _ in range(2)}
        a.alloc(owner=8)
        assert set(a.pages_of(7)) == mine

    def test_assert_all_free(self):
        a = PageAllocator(2)
        p = a.alloc(owner=0)
        with pytest.raises(AssertionError, match="leaked"):
            a.assert_all_free()
        a.free(p, owner=0)
        a.assert_all_free()

    def test_invalid_sizes_rejected(self):
        with pytest.raises(ValueError):
            PageAllocator(0)


class _FakeModel:
    """Stands in for DecoderLM: the cache only needs init_paged_cache."""

    def init_paged_cache(self, n_pages, page_size):
        return {"k": np.zeros((2, n_pages, page_size, 4), np.float32),
                "v": np.zeros((2, n_pages, page_size, 4), np.float32)}


def _cache(n_pages=8, page_size=4, max_batch=3, max_blocks=4):
    return PagedKVCache(_FakeModel(), PagedCacheConfig(
        n_pages=n_pages, page_size=page_size,
        max_batch=max_batch, max_blocks=max_blocks,
    ))


class TestPagedKVCache:
    def test_ensure_capacity_allocates_blocks_lazily(self):
        c = _cache()
        c.ensure_capacity(0, 1)
        assert c.n_blocks(0) == 1
        c.ensure_capacity(0, 4)   # still one page (page_size=4)
        assert c.n_blocks(0) == 1
        c.ensure_capacity(0, 5)   # crosses the boundary
        assert c.n_blocks(0) == 2
        assert c.allocator.n_free == 6

    def test_block_table_rows_are_disjoint(self):
        c = _cache()
        c.ensure_capacity(0, 8)
        c.ensure_capacity(1, 8)
        row0 = set(c.block_tables[0][c.block_tables[0] >= 0].tolist())
        row1 = set(c.block_tables[1][c.block_tables[1] >= 0].tolist())
        assert row0 and row1 and not (row0 & row1)

    def test_release_recycles_and_clears(self):
        c = _cache()
        c.ensure_capacity(2, 10)
        c.release(2)
        assert c.n_blocks(2) == 0
        assert (c.block_tables[2] == -1).all()
        c.allocator.assert_all_free()

    def test_max_context_enforced(self):
        c = _cache()
        with pytest.raises(ValueError, match="max context"):
            c.ensure_capacity(0, 17)  # 4 blocks * 4 tokens = 16 max

    def test_full_trace_leaves_no_leaks(self):
        """Random admit/grow/release trace: the allocator must end fully
        free and never hand a page to two lanes at once."""
        rng = np.random.default_rng(0)
        c = _cache(n_pages=12, page_size=4, max_batch=4, max_blocks=3)
        lengths = [0] * 4
        for _ in range(300):
            lane = int(rng.integers(0, 4))
            if lengths[lane] and rng.random() < 0.3:
                c.release(lane)
                lengths[lane] = 0
            else:
                want = min(lengths[lane] + int(rng.integers(1, 5)), 12)
                try:
                    c.ensure_capacity(lane, want)
                    lengths[lane] = want
                except OutOfPages:
                    c.release(lane)
                    lengths[lane] = 0
            live = c.block_tables[c.block_tables >= 0]
            assert len(live) == len(set(live.tolist()))  # no aliased pages
            assert c.allocator.n_allocated == len(live)
        for lane in range(4):
            if lengths[lane]:
                c.release(lane)
        c.allocator.assert_all_free()
        assert c.allocator.n_free == 12
