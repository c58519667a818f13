"""The manager of a model whose layers attend some over a sliding
window and some over everything (`kv_cache.WindowedKVCache`, ISSUE
35): two pools and two tables a slot behind the one interface the
scheduler asks. The window half's table is a ring: pages go back to
the free list as the window passes them and their columns take the
pages ahead."""

import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import (PagedKVCache, RingKVCache,
                                              WindowedKVCache, ring_columns)
from deepspeed_tpu.monitor import memory as memory_mod

PAGE, WINDOW, SPAN, SLOTS = 4, 10, 6, 3


def make(full_pages=40, ledger=None, max_pages=16):
    common = dict(n_head=2, head_dim=8, page_size=PAGE, max_slots=SLOTS,
                  dtype=np.float32, ledger=ledger)
    ring = ring_columns(WINDOW, PAGE, SPAN)
    window = RingKVCache(WINDOW, SPAN, n_layer=3,
                         num_pages=SLOTS * ring + 1,
                         category=memory_mod.CAT_KV_WINDOW, **common)
    full = PagedKVCache(n_layer=1, num_pages=full_pages,
                        max_pages_per_slot=max_pages, **common)
    return WindowedKVCache(full, window)


def held(cache, slot):
    """{logical page: physical page} that the ring's table shows."""
    w = cache.window
    first = w._first[slot]
    return {first + i: int(w.tables[slot, (first + i) % w.ring])
            for i in range(len(w._pages[slot]))}


@pytest.mark.parametrize("window, page, span, want", [
    (2048, 128, 512, 21), (2048, 128, 4, 18), (10, 4, 6, 5), (24, 8, 16, 6),
    (8, 8, 1, 2)])
def test_ring_columns_hold_every_page_a_group_of_launches_sees(
        window, page, span, want):
    assert ring_columns(window, page, span) == want
    # by enumeration: queries [t, t + span) see keys from t - window +
    # 1 to t + span - 1, wherever t lies in its page
    most = max(((t + span - 1) // page - max(t - window + 1, 0) // page + 1)
               for t in range(0, 4 * window + 3 * page))
    assert most <= want <= most + 1


def test_release_as_the_window_passes_and_ring_reuse():
    cache = make()
    cache.admit(0, 60, "a")
    ring = cache.window.ring
    assert ring == 5 and cache.window.tables.shape == (SLOTS, ring)
    # prefill in chunks of SPAN, as the scheduler asks
    for start in range(0, 30, SPAN):
        cache.ensure(0, start + SPAN, queries_from=start)
        lo = max(start - WINDOW + 1, 0)
        pages = held(cache, 0)
        assert min(pages) == lo // PAGE
        assert max(pages) == (start + SPAN - 1) // PAGE
        assert len(pages) <= ring and 0 not in pages.values()
        assert len(set(pages.values())) == len(pages)
        # the full layers keep everything
        assert cache.full.allocated_pages(0) == -(-(start + SPAN) // PAGE)
    occ = cache.occupancy()
    assert occ["kv_pages_full_in_use"] == 8
    assert occ["kv_pages_window_in_use"] + occ["kv_pages_window_released"] \
        == 8
    assert occ["kv_pages_window_released"] == (24 - WINDOW + 1) // PAGE
    # decode, a block of 2 steps a fence: the ring wraps again and
    # again and never holds more than its columns
    free_before = cache.window.free_pages()
    for pos in range(30, 58, 2):
        cache.ensure(0, pos + 2, queries_from=pos)
        assert len(held(cache, 0)) <= ring
    assert cache.window.free_pages() >= free_before - 1
    assert cache.window.released_pages() == (56 - WINDOW + 1) // PAGE
    # a released page's column is zero or taken by a page ahead
    used = set(held(cache, 0).values())
    assert set(cache.window.tables[0].tolist()) - {0} == used
    cache.free(0)
    assert cache.occupancy() == {
        "kv_pages_full_in_use": 0, "kv_pages_window_in_use": 0,
        "kv_pages_window_released": 0, "kv_pages_free": 39}
    assert cache.window.free_pages() == SLOTS * ring


def test_a_span_wider_than_the_ring_is_refused_with_the_reason():
    cache = make()
    cache.admit(0, 60)
    with pytest.raises(RuntimeError, match="where the coming queries begin"):
        cache.ensure(0, 40)           # no queries_from: nothing released


def test_rollback_across_a_release():
    cache = make()
    cache.admit(1, 60)
    for start in range(0, 24, SPAN):
        cache.ensure(1, start + SPAN, queries_from=start)
    cache.ensure(1, 30, queries_from=24)
    before = held(cache, 1)
    released = cache.window.released_pages()
    assert released == (24 - WINDOW + 1) // PAGE == 3
    # a rejected suffix: back to 21 tokens, which lies AHEAD of what
    # the window passed; the released pages stay released
    freed = cache.rollback(1, 21)
    after = held(cache, 1)
    assert freed == (8 - 6) * 2          # both pools gave two pages back
    assert cache.window.released_pages() == released
    assert set(after) == {p for p in before if p < 6}
    assert all(after[p] == before[p] for p in after)
    # columns of the pages given back are cleared
    assert (np.sort(cache.window.tables[1])[-len(after):] ==
            np.sort(list(after.values()))).all()
    assert (cache.window.tables[1] == 0).sum() == \
        cache.window.ring - len(after)
    # and the slot grows again from there
    cache.ensure(1, 30, queries_from=21)
    assert set(held(cache, 1)) == {p for p in before if p >= 3}
    # back behind the window: what is held goes, nothing breaks
    n = len(held(cache, 1))
    assert cache.window.rollback(1, 4) == n and not held(cache, 1)
    cache.free(1)
    assert cache.window.free_pages() == SLOTS * cache.window.ring


def test_admission_reserves_the_worst_case_and_the_ring():
    cache = make(full_pages=21)              # 20 usable pages
    assert cache.reservation(60) == {"kv_pages_reserved": 15,
                                     "kv_pages_window_reserved": 5}
    # a short request reserves less than the ring
    assert cache.reservation(7) == {"kv_pages_reserved": 2,
                                    "kv_pages_window_reserved": 2}
    assert cache.can_admit(60)
    cache.admit(0, 60)
    assert cache.reserved_tokens(0) == 60
    # the full pool's worst case is what refuses the next one
    assert not cache.can_admit(24) and cache.can_admit(20)
    with pytest.raises(RuntimeError, match="full layers"):
        cache.admit(1, 24)
    cache.admit(1, 20)
    # every slot's ring is there: the window pool never refuses
    assert cache.window.can_admit(10 ** 6)
    cache.free(0), cache.free(1)


def test_never_fits():
    cache = make(full_pages=21, max_pages=16)
    assert cache.never_fits(60) is None
    assert "exceeds the pool's" in cache.never_fits(16 * PAGE + 1)
    small = make(full_pages=9)
    assert "exceeds the pool's" in small.never_fits(40)


def test_attended_counts_each_pools_walk():
    cache = make()
    active = np.asarray([True, False, True])
    pos = np.asarray([3, 99, 30])
    got = cache.attended(active, pos)
    # slot 0: one page in both; slot 2: 8 pages whole, the window's
    # (21 .. 30) pages 5 .. 7
    assert got == {"kv_pages_attended": 1 + 8,
                   "kv_pages_window_attended": 1 + 3}


def test_the_ledger_holds_both_pools_under_their_categories():
    ledger = memory_mod.MemoryLedger()
    cache = make(ledger=ledger)
    cache.admit(0, 60, "r")
    for start in range(0, 24, SPAN):
        cache.ensure(0, start + SPAN, queries_from=start)
    rows = ledger.category_breakdown(memory_mod.CAT_KV_WINDOW)
    assert sum(rows.values()) == cache.window.pool_bytes
    assert sum(ledger.category_breakdown(memory_mod.CAT_KV).values()) == \
        cache.full.pool_bytes
    occ = cache.ledger_occupancy()
    assert occ["kv_pages_window_in_use"] == cache.window.pages_in_use() == 4
    assert occ["kv_pages_full_in_use"] == 6
    cache.free(0)
    assert set(ledger.category_breakdown(memory_mod.CAT_KV_WINDOW)) == \
        {"pool.unallocated"}
