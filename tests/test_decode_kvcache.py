"""Unit tests for the paged KV-cache's host-side accounting
(serve/decode/kvcache.py): free-list alloc/free conservation, admission
exhaustion, double-free detection, and the slot page-table lifecycle."""

import numpy as np
import pytest

from theanompi_tpu.serve.decode.kvcache import (
    FreeList,
    KVExhausted,
    PagedKVCache,
    pages_needed,
)


def test_pages_needed():
    assert pages_needed(0, 4) == 0
    assert pages_needed(1, 4) == 1
    assert pages_needed(4, 4) == 1
    assert pages_needed(5, 4) == 2
    assert pages_needed(17, 16) == 2


def test_freelist_alloc_free_roundtrip():
    fl = FreeList(4)
    assert fl.n_free == 4 and fl.n_used == 0
    pages = fl.alloc(3)
    assert sorted(pages) == [0, 1, 2]
    assert fl.n_free == 1 and fl.n_used == 3
    assert fl.pages_out_total == 3 and fl.pages_in_total == 0
    fl.free(pages)
    assert fl.n_free == 4
    assert fl.pages_in_total == 3
    assert fl.conserved()


def test_freelist_exhaustion_is_atomic():
    fl = FreeList(4)
    fl.alloc(3)
    with pytest.raises(KVExhausted):
        fl.alloc(2)
    # the failed alloc must not have leaked the remaining free page
    assert fl.n_free == 1
    assert fl.pages_out_total == 3


def test_freelist_double_free_raises():
    fl = FreeList(4)
    pages = fl.alloc(2)
    fl.free(pages)
    with pytest.raises(ValueError, match="not outstanding"):
        fl.free([pages[0]])
    with pytest.raises(ValueError, match="not outstanding"):
        fl.free([99])
    assert not fl.conserved() or fl.n_free == 4  # state still coherent


def test_freelist_alloc_zero():
    fl = FreeList(2)
    assert fl.alloc(0) == []
    assert fl.conserved()


def test_cache_reserve_release_lifecycle():
    cache = PagedKVCache(
        n_layers=1, page_size=4, n_pages=8, max_seqs=2, max_pages_per_seq=4,
        k_page=(4, 4), v_page=(4, 4),
    )
    # worst-case reservation: 6 positions over page_size 4 -> 2 pages
    pages = cache.reserve(0, 6)
    assert len(pages) == 2
    assert cache.pages_used == 2
    row = cache.page_tables[0]
    assert list(row[:2]) == pages
    # unowned tail points at scratch
    assert (row[2:] == cache.scratch).all()
    # double reservation of a live slot is a scheduler bug
    with pytest.raises(ValueError, match="already holds"):
        cache.reserve(0, 1)
    assert cache.release(0) == 2
    assert (cache.page_tables[0] == cache.scratch).all()
    assert cache.free_list.conserved()
    # release is idempotent for an empty slot
    assert cache.release(0) == 0


def test_cache_reserve_exhaustion_and_slot_bound():
    cache = PagedKVCache(
        n_layers=1, page_size=4, n_pages=4, max_seqs=2, max_pages_per_seq=4,
        k_page=(4, 4), v_page=(4, 4),
    )
    with pytest.raises(KVExhausted, match="at most"):
        cache.reserve(0, 17)  # 5 pages > max_pages_per_seq
    cache.reserve(0, 16)  # all 4 pages
    with pytest.raises(KVExhausted):
        cache.reserve(1, 1)
    cache.release(0)
    assert cache.free_list.conserved()


def test_cache_release_all():
    cache = PagedKVCache(
        n_layers=1, page_size=2, n_pages=6, max_seqs=3, max_pages_per_seq=2,
        k_page=(2, 4), v_page=(2, 4),
    )
    cache.reserve(0, 3)
    cache.reserve(2, 4)
    assert cache.pages_used == 4
    assert cache.release_all() == 4
    assert cache.pages_free == 6
    assert cache.free_list.conserved()


def test_cache_pool_shapes_fixed():
    cache = PagedKVCache(
        n_layers=3, page_size=4, n_pages=5, max_seqs=2, max_pages_per_seq=4,
        k_page=(4, 16), v_page=(4, 16),
    )
    # scratch page rides at index n_pages: pool holds n_pages + 1
    assert cache.k_pool.shape == (3, 6, 4, 16)
    assert cache.v_pool.shape == (3, 6, 4, 16)
    assert cache.scratch == 5
    assert cache.max_context == 16
    assert cache.page_tables.dtype == np.int32
