"""PagedKVCache rollback tests (ISSUE 18 satellite).

The rejected-suffix rollback of speculative decoding is pure host
accounting — no page data moves — so these tests pin the allocator
invariants speculation leans on, independent of any engine:

  * rewinding `kv_limit` across a page boundary releases exactly the
    tail pages and resets their table columns to the scratch page;
  * re-advancing into a previously-rolled-back region pops the SAME
    physical pages into the SAME table columns (the LIFO free list's
    reversed() push is what guarantees it);
  * ledger byte accounting after rollback: the `kv_cache` (and, with
    a draft attached, `kv_cache_draft`) category totals stay equal to
    their pool bytes through arbitrary rollback/regrow churn;
  * a rollback that trims nothing is a true no-op (no table_version
    bump, so the engine skips the device table upload).
"""

import numpy as np
import pytest

from deepspeed_tpu.inference import PagedKVCache
from deepspeed_tpu.monitor.memory import CAT_KV, CAT_KV_DRAFT, MemoryLedger


def _cache(ledger=None, draft_layers=0, n_head=4, head_dim=16,
           n_kv_head=None):
    kv = {} if n_kv_head is None else {"n_kv_head": n_kv_head}
    cache = PagedKVCache(n_layer=2, n_head=n_head, head_dim=head_dim,
                         num_pages=32, page_size=4, max_slots=4,
                         max_pages_per_slot=8, dtype=np.float32,
                         ledger=ledger, **kv)
    if draft_layers:
        cache.attach_draft(draft_layers)
    return cache


@pytest.mark.parametrize("tokens_before,tokens_after,freed", [
    (10, 5, 1),    # 3 pages -> 2: rewind crosses one page boundary
    (16, 1, 3),    # deep rewind to the first page
    (13, 12, 1),   # one token back across the 12|13 boundary
    (13, 9, 1),    # both land in page 3's span -> only page 4 goes
    (8, 8, 0),     # same count: nothing to trim
    (8, 11, 0),    # "rollback" forward never frees (ensure grows)
])
def test_rollback_releases_exact_tail_pages(tokens_before, tokens_after,
                                            freed):
    cache = _cache()
    cache.admit(0, 17, name="a")
    cache.ensure(0, tokens_before)
    before_pages = list(cache.tables[0])
    n_before = cache.allocated_pages(0)
    ver = cache.table_version
    got = cache.rollback(0, tokens_after)
    assert got == freed
    assert cache.allocated_pages(0) == n_before - freed
    keep = cache.pages_for_tokens(min(tokens_after, tokens_before))
    # kept columns untouched, trimmed columns back to scratch page 0
    assert list(cache.tables[0][:keep]) == before_pages[:keep]
    assert (cache.tables[0][n_before - freed:] == 0).all()
    if freed == 0:
        assert cache.table_version == ver, \
            "a no-op rollback must not bump table_version"
    else:
        assert cache.table_version == ver + 1


def test_readvance_reassigns_same_pages_same_columns():
    """LIFO regrowth: after a rollback, growing the SAME slot back
    re-pops the very pages that were trimmed, page-for-page, so the
    device table row is bit-identical to before the rollback — the
    property that lets speculation skip any K/V copying."""
    cache = _cache()
    cache.admit(0, 24, name="a")
    cache.ensure(0, 23)                   # 6 pages
    row_before = list(cache.tables[0])
    cache.rollback(0, 6)                  # keep 2, free 4
    assert cache.allocated_pages(0) == 2
    cache.ensure(0, 23)
    assert list(cache.tables[0]) == row_before
    # repeated churn at a different depth, same invariant
    cache.rollback(0, 17)
    cache.ensure(0, 21)
    assert list(cache.tables[0]) == row_before


def test_rollback_interleaved_with_other_slots():
    """Rollback's freed pages are ordinary free-list pages: another
    slot may take them, after which regrowth gets different physical
    pages — tables stay consistent and no page is double-assigned."""
    cache = _cache()
    cache.admit(0, 16, name="a")
    cache.admit(1, 16, name="b")
    cache.ensure(0, 16)
    cache.rollback(0, 4)                  # frees 3 of slot 0's pages
    cache.ensure(1, 12)                   # slot 1 adopts them (LIFO)
    cache.ensure(0, 16)                   # slot 0 regrows from elsewhere
    a = [p for p in cache.tables[0] if p != 0]
    b = [p for p in cache.tables[1] if p != 0]
    assert len(a) == 4 and len(b) == 3
    assert not set(a) & set(b), "a physical page leaked to two slots"


@pytest.mark.parametrize("n_head, head_dim, n_kv_head, lanes", [
    (4, 16, None, 128),    # a row of 4 heads x 16 takes one 128-lane tile
    (4, 16, 4, 128),       # G = 1 said aloud: today's pool, byte for byte
    (20, 128, 4, 512),     # grouped-query heads (ISSUE 31): the pools hold
    #                        the 4 key/value heads, four lane tiles, no pad
    (6, 48, 2, 128),       # 2 x 48 = 96 lanes padded to a tile
    (6, 48, None, 384),    # the same heads ungrouped: 288 padded to 384
], ids=["4x16", "4over4x16", "20over4x128", "6over2x48", "6x48"])
def test_rollback_ledger_accounting_with_draft_category(n_head, head_dim,
                                                        n_kv_head, lanes):
    """Through rollback/regrow churn both ledger categories keep
    total == pool bytes, and the per-request entries track the page
    count in each category's own page-byte unit. The unit follows the
    key/value head count: a page holds `n_kv_head * head_dim` lanes a
    token, padded to whole lane tiles."""
    ledger = MemoryLedger()
    cache = _cache(ledger=ledger, draft_layers=1, n_head=n_head,
                   head_dim=head_dim, n_kv_head=n_kv_head)
    # independent arithmetic: flagship 2 layers, draft 1 layer
    assert cache.lanes == lanes and cache.n_kv_head == (n_kv_head or n_head)
    assert cache.pool_shape(2) == (2, 32, 4, lanes)
    page_bytes = 2 * 2 * 4 * lanes * 4
    draft_page_bytes = 2 * 1 * 4 * lanes * 4
    assert cache.page_bytes == page_bytes
    assert cache.draft_page_bytes == draft_page_bytes

    def totals():
        t = ledger.totals()["hbm"]
        return t.get(CAT_KV, 0), t.get(CAT_KV_DRAFT, 0)

    assert totals() == (cache.pool_bytes, cache.draft_pool_bytes)
    cache.admit(0, 17, name="a")
    cache.ensure(0, 15)                   # 4 pages
    assert totals() == (cache.pool_bytes, cache.draft_pool_bytes)
    tops = {(b["category"], b["name"]): b["bytes"]
            for b in ledger.top_buffers(32)}
    assert tops[(CAT_KV, "request.s0.a")] == 4 * page_bytes
    assert tops[(CAT_KV_DRAFT, "request.s0.a")] == 4 * draft_page_bytes
    cache.rollback(0, 6)                  # 4 pages -> 2
    assert totals() == (cache.pool_bytes, cache.draft_pool_bytes)
    tops = {(b["category"], b["name"]): b["bytes"]
            for b in ledger.top_buffers(32)}
    assert tops[(CAT_KV, "request.s0.a")] == 2 * page_bytes
    assert tops[(CAT_KV_DRAFT, "request.s0.a")] == 2 * draft_page_bytes
    cache.ensure(0, 17)
    assert totals() == (cache.pool_bytes, cache.draft_pool_bytes)
    cache.free(0)
    assert totals() == (cache.pool_bytes, cache.draft_pool_bytes)
    tops = {b["name"] for b in ledger.top_buffers(32)}
    assert "request.s0.a" not in tops


def test_rollback_unadmitted_slot_raises():
    cache = _cache()
    with pytest.raises(ValueError, match="not admitted"):
        cache.rollback(2, 4)
