"""Device-resident paged KV cache (PagedAttention-style block tables).

The serving engine never materialises one contiguous [T, H, D] KV
buffer per request — at high slot counts the padding-to-max waste is
the first thing that OOMs a serving chip. Instead a single preallocated
pool of fixed-size pages

    k_pool / v_pool : [n_layer, num_pages, page_size, lanes]

(one token's K or V of one layer on the lanes, `lanes` = n_kv_head *
head_dim rounded up to a whole number of the chip's 128-lane tiles:
the layout the compiled programs scatter into in place and the decode
kernel copies single pages out of, see `engine.scan_layers` and
`ops/transformer/paged_decode_attention.py`) is shared by every
request; each request slot
owns a page table (row of physical page ids) and positions map to
(physical page, offset) by plain index math inside the compiled
programs. Physical page 0 is a reserved scratch page: masked writes
(inactive decode slots, prefill pad rows) are diverted there instead
of being predicated away, so the compiled step stays branch-free.

Allocation is host-side and happens only at serving fences (request
admission / chunk reservation / finish) — never inside the dispatch
loop. Admission reserves a request's worst-case page count up front
(`can_admit`), so a request that was admitted can never fail an
allocation mid-flight; pages are still *assigned* incrementally as the
sequence actually grows, which is what the ledger reports.

Ledger integration (the PR-8 contract): the pool registers itself
under the `kv_cache` category — one dynamic `pool.unallocated` entry
plus one dynamic entry per live request — so the category total always
equals the true preallocated pool bytes while `top_buffers` and the
category meta give per-request byte attribution, and `oom_hints` can
name `inference.kv_cache.num_pages` when the cache dominates.

A second kind of slot state lives beside the pages
(`RecurrentStateCache`): a model whose layers keep a fixed-size
recurrent state per request (power retention, `models/brumby.py`; a
state-space mixer's matrix and its convolution's carried rows,
`models/falcon_h1.py`) owns one block of it per slot, sized once and
never grown, in the shapes the model's config gives. A model whose
every layer keeps BOTH (attention over pages and a state-space mixer
side by side) is served by `PagedStateCache`, the two managers behind
one. A model whose layers attend some over a sliding window and some
over everything is served by `WindowedKVCache`: two pools and two
tables a slot, the window layers' table a ring whose pages go back to
the free list as the window passes them. A model that attends by
latent attention keeps ONE pool of one compressed row a token
(`LatentKVCache`, the paged manager counting one pool). A model whose
layers keep DIFFERENT things (a state in some, a ring in others, one
layer of pages that several layers read) is served by `HybridKVCache`:
the state's, the ring's and the paged manager behind one. A model
whose every layer keeps pages OR state is `PagedStateCache`'s again,
its halves over different counts of layers. All of them answer the
scheduler's one interface: `can_admit`, `admit`, `ensure`, `free`,
`reserved_tokens`, `never_fits`, `reservation`, `occupancy`,
`attended`, `slot_operand` (and `rollback`, which recurrent state
refuses). `ensure` is told where the coming launches' queries begin
(`queries_from`); only a ring of pages does anything with it.
"""

import numpy as np

from deepspeed_tpu.monitor import memory as memory_mod
from deepspeed_tpu.ops.transformer import latent_attention
from deepspeed_tpu.ops.transformer.paged_decode_attention import padded_lanes
from deepspeed_tpu.ops.transformer.paged_prefill_attention import walked_keys


class PagedKVCache:
    """Host-side page allocator + device pool shapes for one engine.

    The device pool arrays themselves live in the engine's decode
    state (they are donated through the compiled steps); this object
    owns the page *tables* (numpy source of truth, staged to device by
    the engine after fence-side mutations) and the free-list math.
    """

    def __init__(self, n_layer, n_head, head_dim, num_pages, page_size,
                 max_slots, max_pages_per_slot, dtype=np.float32,
                 ledger=None, n_kv_head=None, category=memory_mod.CAT_KV,
                 pools=2):
        if max_pages_per_slot < 1:
            raise ValueError(
                f"max_pages_per_slot must be >= 1, got {max_pages_per_slot}")
        if num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is the reserved scratch "
                f"page), got {num_pages}")
        self.n_layer = int(n_layer)
        self.n_head = int(n_head)
        # grouped-query heads: the pools hold the key/value heads only
        self.n_kv_head = int(n_head if n_kv_head is None else n_kv_head)
        self.head_dim = int(head_dim)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.dtype = np.dtype(dtype)
        # one token's K (or V) of one layer as the pools hold it: the
        # chip tiles the minor-most dimension by 128 lanes, so a row of
        # 1,600 takes 1,664 there whether the shape says so or not; the
        # shape says so, and a page is a whole number of tiles that the
        # decode kernel can copy alone
        self.lanes = padded_lanes(self.n_kv_head * self.head_dim)
        # bytes of ONE page across the pools (K and V; a latent cache
        # holds one) and all layers: the unit every accounting
        # statement below is phrased in
        self.page_bytes = (int(pools) * self.n_layer * self.page_size *
                           self.lanes * self.dtype.itemsize)
        self.pool_bytes = self.num_pages * self.page_bytes
        # page 0 = scratch; pages 1..num_pages-1 allocatable (LIFO free
        # list: recently freed pages are re-assigned first, which keeps
        # the working set compact)
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._reserved = {}        # slot -> reserved page credit (int)
        self._pages = {}           # slot -> [physical page ids]
        self._names = {}           # slot -> ledger entry name
        # host source of truth for the device page tables; scratch page
        # 0 everywhere a slot has no page yet. `table_version` bumps on
        # every mutation so the engine uploads the table only when it
        # actually changed (push_tables is called liberally at fences)
        self.tables = np.zeros((self.max_slots, self.max_pages_per_slot),
                               np.int32)
        self.table_version = 0
        self._ledger = ledger
        self._category = category
        self._ledger_tokens = {}
        # speculative-decoding draft pool (attach_draft): same page
        # tables/allocator, fewer layers, its own ledger category
        self.draft_n_layer = 0
        self.draft_page_bytes = 0
        self.draft_pool_bytes = 0
        self._draft_ledger_tokens = {}
        if ledger is not None:
            ledger.register_dynamic(
                category, "pool.unallocated",
                lambda: self.pool_bytes - self.allocated_bytes(),
                meta={"num_pages": self.num_pages,
                      "page_size": self.page_size})

    def attach_draft(self, n_layer_draft):
        """Declare the speculative draft model's KV pool: it shares
        this cache's page tables and free-list verbatim (one allocator,
        one admission decision), so the only new accounting is bytes —
        a second ledger category (`kv_cache_draft`) with the same
        unallocated + per-request split, phrased in draft page bytes
        (the flagship's page bytes scaled to the draft's layer count)."""
        self.draft_n_layer = int(n_layer_draft)
        self.draft_page_bytes = (2 * self.draft_n_layer * self.page_size *
                                 self.lanes * self.dtype.itemsize)
        self.draft_pool_bytes = self.num_pages * self.draft_page_bytes
        if self._ledger is not None:
            self._ledger.register_dynamic(
                memory_mod.CAT_KV_DRAFT, "pool.unallocated",
                lambda: self.draft_pool_bytes -
                self.pages_in_use() * self.draft_page_bytes,
                meta={"num_pages": self.num_pages,
                      "page_size": self.page_size,
                      "n_layer_draft": self.draft_n_layer})

    def pool_shape(self, n_layer):
        """Shape of ONE device pool (K or V) of `n_layer` layers: one
        token's K (or V) of one layer is the minor-most row (`lanes`:
        its n_kv_head * head_dim values, then zeros up to the lane tile),
        so the pool has one natural layout inside and outside the
        compiled programs' layer scan."""
        return (int(n_layer), self.num_pages, self.page_size, self.lanes)

    # -- accounting -----------------------------------------------------
    def pages_for_tokens(self, n_tokens):
        """Pages needed to hold positions [0, n_tokens): the ONE
        ceil-division expression of the capacity contract (tests pin
        ledger bytes against independent uses of this arithmetic)."""
        return -(-int(n_tokens) // self.page_size)

    def free_pages(self):
        return len(self._free)

    def reserved_unallocated(self):
        """Pages promised to admitted requests but not yet assigned
        (admit() and free() keep _reserved/_pages in lockstep)."""
        return sum(max(self._reserved[s] - len(p), 0)
                   for s, p in self._pages.items())

    def slots(self):
        """Admitted slot ids (live requests)."""
        return list(self._pages)

    def reserved_tokens(self, slot):
        """Token capacity of `slot`'s admission reservation."""
        return self._reserved.get(slot, 0) * self.page_size

    def allocated_pages(self, slot):
        return len(self._pages.get(slot, ()))

    def slot_bytes(self, slot):
        return self.allocated_pages(slot) * self.page_bytes

    def allocated_bytes(self):
        return sum(len(p) for p in self._pages.values()) * self.page_bytes

    def pages_in_use(self):
        """Pages currently ASSIGNED to live requests (reservations not
        yet backed by a page don't count — they are promises, not
        bytes in a table row)."""
        return sum(len(p) for p in self._pages.values())

    def utilization(self):
        """Assigned fraction of the allocatable pool (page 0 is
        scratch) — the serving tracker's KV-utilization counter track
        derives the same number from the ledger's `kv_cache` category;
        this is the cache-side twin for tests and hints."""
        return self.pages_in_use() / max(self.num_pages - 1, 1)

    # -- what the scheduler, the engine and the monitor ask of any cache
    kind = "paged"

    def pages_to_reserve(self, n_tokens_worst_case):
        """Pages admission sets aside for that worst case: every one
        it would fill (a ring of pages: no more than the ring)."""
        return self.pages_for_tokens(n_tokens_worst_case)

    def never_fits(self, n_tokens_worst_case):
        """Why a request of that worst case can NEVER be admitted (a
        message), or None: `ServingLoop.submit` rejects it at once
        instead of waiting for an eviction that cannot help."""
        usable = min(self.max_pages_per_slot, self.num_pages - 1)
        need = self.pages_to_reserve(n_tokens_worst_case)
        if need > usable:
            return (f"worst case {need} pages exceeds the pool's {usable} "
                    "usable pages (raise inference.kv_cache.num_pages)")
        return None

    def reservation(self, n_tokens_worst_case):
        """What admission sets aside, as the `request_admitted` event
        reports it."""
        return {"kv_pages_reserved":
                int(self.pages_to_reserve(n_tokens_worst_case))}

    def occupancy(self):
        """What every fence reports of the cache."""
        return {"kv_pages_in_use": int(self.pages_in_use()),
                "kv_pages_free": int(self.free_pages())}

    # what `prefill_keys` counts, as the fence rows name it
    PREFILL_KEYS = ("kv_prefill_keys_attended", "kv_prefill_keys_tabled")

    def prefill_keys(self, start, n, first=0):
        """(attended, tabled) of a prefill launch of `n` prompt tokens
        from position `start`, in one layer of this pool: the keys its
        attention walks (`paged_prefill_attention`: whole blocks of
        pages from the page of `first` to the page of the launch's
        last key), and the keys of the slot's whole table row, which a
        launch gathered and attended to whatever the slot held before
        ISSUE 42. Host arithmetic; the engine sums it over its
        launches and `attended` reports a fence's."""
        columns = self.max_pages_per_slot
        return (walked_keys(start + n - 1, self.page_size, columns, first),
                columns * self.page_size)

    def attended(self, active, pos, launches=0, advanced=0, prefill_rows=0,
                 prefill_tokens=0, prefill_keys=()):
        """How far the decode kernel engages at the next launch, from
        what the fence fetched (`active`, `pos` of every slot; host
        arrays): the pages it walks, ceil((pos + 1) / page) summed
        over the live slots, and their share of the window the
        gathered path attended to whatever was live (max_slots x
        max_pages_per_slot). And how far prefill's attention engaged
        over the fence just closed: the keys its launches walked
        against the keys of their table rows (`prefill_keys`, summed
        by the engine). The fence's own launches, decode's and
        prefill's, are not read here (recurrent state counts them)."""
        pages = int((-(-(pos[active] + 1) // self.page_size)).sum())
        return {"kv_pages_attended": pages,
                "kv_pages_attended_share": round(
                    pages / (self.max_slots * self.max_pages_per_slot), 4),
                **{k: int(v) for k, v in zip(self.PREFILL_KEYS,
                                             prefill_keys)}}

    def ledger_occupancy(self):
        """`occupancy` with the utilization, as the serving tracker
        reports it: derived from the memory ledger's `kv_cache`
        category, not from the page tables (the per-request dynamic
        entries are the in-use bytes, `pool.unallocated` the rest: pure
        host reads of registered shape math). Two independent
        accounting chains; tests/test_inference.py holds them equal."""
        if self._ledger is None:
            in_use = self.pages_in_use()
        else:
            rows = self._ledger.category_breakdown(self._category)
            in_use = int(sum(b for name, b in rows.items()
                             if name != "pool.unallocated") //
                         max(self.page_bytes, 1))
        allocatable = max(self.num_pages - 1, 1)
        return {"kv_pages_in_use": in_use,
                "kv_pages_free": max(allocatable - in_use, 0),
                "kv_page_utilization": round(in_use / allocatable, 4)}

    def utilization_counter(self, occupancy):
        """(name, values) of the trace export's counter track."""
        return "kv_page_utilization", {
            "in_use": occupancy["kv_pages_in_use"],
            "free": occupancy["kv_pages_free"]}

    def slot_operand(self, slot):
        """What the prefill program is handed to find `slot`'s cache:
        its page-table row."""
        return self.tables[slot]

    # -- admission / growth / release -----------------------------------
    def can_admit(self, n_tokens_worst_case):
        """True when a request that may grow to n_tokens_worst_case
        positions fits: its worst-case pages AND every other live
        request's still-unassigned reservation must be coverable by the
        free list — admitted requests never fail mid-flight."""
        need = self.pages_to_reserve(n_tokens_worst_case)
        if need > self.max_pages_per_slot:
            return False
        return need + self.reserved_unallocated() <= len(self._free)

    def admit(self, slot, n_tokens_worst_case, name=None):
        """Reserve worst-case capacity for `slot` (no pages assigned
        yet) and open its ledger entry."""
        if slot in self._pages or slot in self._reserved:
            raise ValueError(f"slot {slot} is already admitted")
        if not self.can_admit(n_tokens_worst_case):
            raise RuntimeError(
                f"kv cache cannot admit {n_tokens_worst_case} tokens: "
                f"{len(self._free)} free pages, "
                f"{self.reserved_unallocated()} already reserved "
                "(raise inference.kv_cache.num_pages)")
        self._reserved[slot] = self.pages_to_reserve(n_tokens_worst_case)
        self._pages[slot] = []
        self._names[slot] = name or f"slot{slot}"
        if self._ledger is not None:
            # the slot id keys the entry: request ids are caller-chosen
            # and two live requests may share one — a name collision
            # would let the first free() release the second's entry and
            # break the category-total == pool-bytes invariant
            self._ledger_tokens[slot] = self._ledger.register_dynamic(
                self._category,
                f"request.s{slot}.{self._names[slot]}",
                (lambda s: lambda: self.slot_bytes(s))(slot),
                meta={"slot": int(slot),
                      "request": self._names[slot]})
            if self.draft_n_layer:
                self._draft_ledger_tokens[slot] = \
                    self._ledger.register_dynamic(
                        memory_mod.CAT_KV_DRAFT,
                        f"request.s{slot}.{self._names[slot]}",
                        (lambda s: lambda: self.allocated_pages(s) *
                         self.draft_page_bytes)(slot),
                        meta={"slot": int(slot),
                              "request": self._names[slot]})

    def ensure(self, slot, n_tokens, queries_from=None):
        """Assign pages so `slot` can hold positions [0, n_tokens).
        Within the admission reservation this cannot fail; beyond it,
        it raises (the scheduler sizes reservations so it never asks).
        Every key stays visible, so where the coming queries begin
        (`queries_from`) changes nothing here."""
        if slot not in self._pages:
            raise ValueError(f"slot {slot} is not admitted")
        need = self.pages_for_tokens(n_tokens)
        pages = self._pages[slot]
        if need > self._reserved[slot]:
            raise RuntimeError(
                f"slot {slot}: {n_tokens} tokens exceeds the admission "
                f"reservation of {self._reserved[slot]} pages")
        while len(pages) < need:
            phys = self._free.pop()
            pages.append(phys)
            self.tables[slot, len(pages) - 1] = phys
            self.table_version += 1
        return pages

    def rollback(self, slot, n_tokens):
        """Rewind `slot` to exactly the pages needed for positions
        [0, n_tokens) — the rejected-suffix rollback of speculative
        decoding. NO page data is copied or cleared: the device-side
        kv_limit (the slot's `pos`) is what masks stale K/V, so
        rollback is pure host accounting — trimmed pages go back on
        the LIFO free list (a re-advance pops the SAME physical pages
        into the SAME table columns) and the freed table columns reset
        to the scratch page. Returns the number of pages released; a
        rollback that trims nothing is a no-op (no table_version bump,
        no table upload)."""
        if slot not in self._pages:
            raise ValueError(f"slot {slot} is not admitted")
        need = self.pages_for_tokens(n_tokens)
        pages = self._pages[slot]
        if need >= len(pages):
            return 0
        freed = pages[need:]
        del pages[need:]
        # reversed: the highest-position page ends up on top of the
        # LIFO list, so regrowth reassigns page-for-page identically
        self._free.extend(reversed(freed))
        self.tables[slot, need:need + len(freed)] = 0
        self.table_version += 1
        return len(freed)

    def free(self, slot):
        """Return `slot`'s pages to the free list, drop its
        reservation, close its ledger entry, and reset its table row to
        the scratch page."""
        pages = self._pages.pop(slot, [])
        self._free.extend(reversed(pages))
        self._reserved.pop(slot, None)
        self._names.pop(slot, None)
        self.tables[slot, :] = 0
        self.table_version += 1
        token = self._ledger_tokens.pop(slot, None)
        if token is not None and self._ledger is not None:
            self._ledger.release(token)
        dtoken = self._draft_ledger_tokens.pop(slot, None)
        if dtoken is not None and self._ledger is not None:
            self._ledger.release(dtoken)
        return len(pages)


class LatentKVCache(PagedKVCache):
    """The pages of a model that attends by multi-head latent
    attention (`models/sarvam_mla.py`): ONE pool

        latent_pool : [n_layer, num_pages, page_size, lanes]

    whose row is what a token leaves in a layer for ALL heads, the
    compressed vector and the shared rotary key (`row_values`, the
    config's `latent_row`: 576 values where 64 heads of expanded keys
    and values would be 20,480), zeros from there up to the lane tile.
    Tables, admission, growth and release are the paged cache's, and
    so is the ledger's category; the bytes count one pool, not two,
    and every fence says what the pool holds
    (`kv_latent_bytes_resident`)."""

    def __init__(self, row_values, **kw):
        super().__init__(n_head=1, head_dim=row_values, n_kv_head=1,
                         pools=1, **kw)

    def occupancy(self):
        return dict(super().occupancy(),
                    kv_latent_bytes_resident=int(self.pool_bytes))

    def prefill_keys(self, start, n):
        """(attended, tabled) of a prefill launch, under the paged
        cache's names: the keys of the whole blocks from the slot's
        first key to the launch's last, which both latent forms walk
        (`latent_attention.walked_keys`; what the kernel's query tiles
        skip inside that extent is not taken off), and the keys of the
        slot's table row. Host arithmetic."""
        columns = self.max_pages_per_slot
        return (latent_attention.walked_keys(
            start + n - 1, self.page_size, columns),
            columns * self.page_size)


class RecurrentStateCache:
    """Slot state that is a fixed block per request, not a page list:
    for every layer the arrays the model's config names
    (`state_slot_shapes`: ((shape, dtype), ...) of ONE slot of ONE
    layer), each held as

        [n_layer, max_slots, *shape]

    and sized once at construction: a retention model's float32 matrix
    and normaliser per key/value head (`models/brumby.py`: [Hk, D, d]
    and [Hk, D]), a state-space mixer's convolution rows and state
    matrix (`models/falcon_h1.py`: [3, 5120] and [32, 128, 256]). A
    request is admitted by free slot and its state never grows, so
    `ensure` has nothing to do and there are no page tables. A slot is
    not cleared when it is freed: the compiled programs start a slot
    from zero state when its first chunk (`start == 0`) or, for a
    one-token prompt, its first decode step (`pos == 0`) runs. There
    are no snapshots of state yet, so `rollback` raises (speculative
    decoding is refused at engine construction for such a model).

    Ledger: the whole block is registered under `recurrent_state`, one
    dynamic `slots.unheld` entry plus one per live request, so the
    category total always equals the preallocated bytes."""

    kind = "recurrent"
    table_version = 0                  # no page tables to push

    def __init__(self, n_layer, slot_shapes, max_slots,
                 max_tokens_per_slot, ledger=None):
        self.n_layer = int(n_layer)
        self.slot_shapes = tuple(
            (tuple(int(n) for n in shape), np.dtype(dtype))
            for shape, dtype in slot_shapes)
        self.max_slots = int(max_slots)
        self.max_tokens_per_slot = int(max_tokens_per_slot)
        # bytes of ONE slot across all layers and arrays
        self.slot_state_bytes = self.n_layer * sum(
            int(np.prod(shape)) * dtype.itemsize
            for shape, dtype in self.slot_shapes)
        self.pool_bytes = self.max_slots * self.slot_state_bytes
        self._reserved = {}        # slot -> admitted token capacity
        self._ledger = ledger
        self._ledger_tokens = {}
        if ledger is not None:
            ledger.register_dynamic(
                memory_mod.CAT_STATE, "slots.unheld",
                lambda: self.pool_bytes - self.resident_bytes(),
                meta={"max_slots": self.max_slots,
                      "slot_state_bytes": self.slot_state_bytes})

    def state_shapes(self):
        """Shapes of the device arrays, in the config's order."""
        return tuple((self.n_layer, self.max_slots) + shape
                     for shape, _ in self.slot_shapes)

    def state_dtypes(self):
        return tuple(dtype for _, dtype in self.slot_shapes)

    # -- accounting -----------------------------------------------------
    def slots(self):
        return list(self._reserved)

    def slots_in_use(self):
        return len(self._reserved)

    def free_slots(self):
        return self.max_slots - len(self._reserved)

    def resident_bytes(self):
        """Bytes of state that live requests hold."""
        return len(self._reserved) * self.slot_state_bytes

    def reserved_tokens(self, slot):
        return self._reserved.get(slot, 0)

    def never_fits(self, n_tokens_worst_case):
        if n_tokens_worst_case > self.max_tokens_per_slot:
            return (f"worst case {n_tokens_worst_case} tokens exceeds "
                    f"max_seq_len {self.max_tokens_per_slot}")
        return None

    def reservation(self, n_tokens_worst_case):
        return {"state_bytes_reserved": int(self.slot_state_bytes)}

    def occupancy(self):
        return {"state_slots_in_use": int(self.slots_in_use()),
                "state_slots_free": int(self.free_slots()),
                "state_bytes_resident": int(self.resident_bytes())}

    ledger_occupancy = occupancy       # the manager's own counters

    def prefill_keys(self, start, n):
        return ()

    def attended(self, active, pos, launches=0, advanced=0, prefill_rows=0,
                 prefill_tokens=0, prefill_keys=()):
        """A model of state attends to no pages. What its decode
        kernel moved over the fence just closed: every launch streams
        every slot's state (`launches` x max_slots), and `advanced` of
        those slot-steps belonged to a live request (a live slot takes
        one token a launch, so it is the fence's tokens); their ratio
        is the share of the kernel's traffic that advanced a
        request. And what its prefill took through a slot's state: the
        rows of the fence's prefill launches (`prefill_rows`: launches
        x prefill_chunk, padding and all) and the prompt tokens among
        them (`prefill_tokens`); their ratio is the share of the
        prefill kernel's rows that were a request's."""
        return {"state_slots_streamed": int(launches) * self.max_slots,
                "state_slots_advanced": int(advanced),
                "state_prefill_rows_streamed": int(prefill_rows),
                "state_prefill_tokens": int(prefill_tokens)}

    def utilization_counter(self, occupancy):
        return "state_slot_utilization", {
            "in_use": occupancy["state_slots_in_use"],
            "free": occupancy["state_slots_free"]}

    def allocated_pages(self, slot):
        """A slot of state holds no pages (its block is fixed:
        `state_bytes_resident` counts it)."""
        return 0

    def slot_operand(self, slot):
        """The prefill program finds `slot`'s state by its index."""
        return np.int32(slot)

    # -- admission / release --------------------------------------------
    def can_admit(self, n_tokens_worst_case):
        return self.free_slots() > 0 and \
            self.never_fits(n_tokens_worst_case) is None

    def admit(self, slot, n_tokens_worst_case, name=None):
        if slot in self._reserved:
            raise ValueError(f"slot {slot} is already admitted")
        if not 0 <= slot < self.max_slots:
            raise ValueError(f"slot {slot} is outside the state's "
                             f"{self.max_slots} slots")
        if not self.can_admit(n_tokens_worst_case):
            raise RuntimeError(
                f"recurrent state cannot admit {n_tokens_worst_case} "
                f"tokens: {self.free_slots()} free slots "
                "(raise inference.max_slots)")
        self._reserved[slot] = int(n_tokens_worst_case)
        if self._ledger is not None:
            name = name or f"slot{slot}"
            self._ledger_tokens[slot] = self._ledger.register_dynamic(
                memory_mod.CAT_STATE, f"request.s{slot}.{name}",
                lambda: self.slot_state_bytes,
                meta={"slot": int(slot), "request": name})

    def ensure(self, slot, n_tokens, queries_from=None):
        """Nothing grows; only the admission's bound is held."""
        if slot not in self._reserved:
            raise ValueError(f"slot {slot} is not admitted")
        if n_tokens > self._reserved[slot]:
            raise RuntimeError(
                f"slot {slot}: {n_tokens} tokens exceeds the admission "
                f"reservation of {self._reserved[slot]} tokens")

    def rollback(self, slot, n_tokens):
        raise NotImplementedError(
            "recurrent state cannot be rewound: snapshots of state do "
            "not exist yet")

    def free(self, slot):
        self._reserved.pop(slot, None)
        token = self._ledger_tokens.pop(slot, None)
        if token is not None and self._ledger is not None:
            self._ledger.release(token)
        return 0


class PagedStateCache:
    """Both kinds of slot state side by side, for a model whose every
    layer keeps K/V pages AND a fixed block of recurrent state
    (`models/falcon_h1.py`): `pages` (a `PagedKVCache`) and `state` (a
    `RecurrentStateCache`) behind the one interface. A request is
    admitted only if both have room and holds its part of both until it
    is freed; neither half knows of the other, and every fence row
    carries both halves' counters (`kv_pages_*` and `state_slots_*`).
    State cannot be rewound, so `rollback` is refused as for recurrent
    state alone. Each half has its own count of layers: a model whose
    layers keep pages OR state (`models/nemotron_h.py`, kind
    "paged|state") is served by the same two managers, each sized by
    the layers that keep its half."""

    def __init__(self, pages, state, kind="paged+state"):
        self.pages, self.state, self.kind = pages, state, kind
        self.pool_bytes = pages.pool_bytes + state.pool_bytes
        self.num_pages = pages.num_pages      # the tracker's snapshot

    # the page tables are the paged half's
    tables = property(lambda self: self.pages.tables)
    table_version = property(lambda self: self.pages.table_version)

    def pool_shape(self, n_layer):
        return self.pages.pool_shape(n_layer)

    def state_shapes(self):
        return self.state.state_shapes()

    def state_dtypes(self):
        return self.state.state_dtypes()

    def slots(self):
        return self.state.slots()

    def reserved_tokens(self, slot):
        return min(self.pages.reserved_tokens(slot),
                   self.state.reserved_tokens(slot))

    def allocated_pages(self, slot):
        return self.pages.allocated_pages(slot)

    def never_fits(self, n_tokens_worst_case):
        return self.pages.never_fits(n_tokens_worst_case) or \
            self.state.never_fits(n_tokens_worst_case)

    def reservation(self, n_tokens_worst_case):
        return {**self.pages.reservation(n_tokens_worst_case),
                **self.state.reservation(n_tokens_worst_case)}

    def occupancy(self):
        return {**self.pages.occupancy(), **self.state.occupancy()}

    def ledger_occupancy(self):
        return {**self.pages.ledger_occupancy(),
                **self.state.ledger_occupancy()}

    def prefill_keys(self, start, n):
        return self.pages.prefill_keys(start, n)

    def attended(self, *fence):
        return {**self.pages.attended(*fence), **self.state.attended(*fence)}

    def utilization_counter(self, occupancy):
        """The trace export has one track a cache: the pages' (what
        fills first; the state's slots follow `batch_occupancy`)."""
        return self.pages.utilization_counter(occupancy)

    def slot_operand(self, slot):
        """What the prefill program is handed to find `slot`'s cache:
        its page-table row AND its index into the state."""
        return (self.pages.slot_operand(slot),
                self.state.slot_operand(slot))

    def can_admit(self, n_tokens_worst_case):
        return self.pages.can_admit(n_tokens_worst_case) and \
            self.state.can_admit(n_tokens_worst_case)

    def admit(self, slot, n_tokens_worst_case, name=None):
        if not self.can_admit(n_tokens_worst_case):
            raise RuntimeError(
                f"cache cannot admit {n_tokens_worst_case} tokens: "
                f"{self.pages.free_pages()} free pages, "
                f"{self.pages.reserved_unallocated()} already reserved, "
                f"{self.state.free_slots()} free slots of state")
        self.pages.admit(slot, n_tokens_worst_case, name)
        self.state.admit(slot, n_tokens_worst_case, name)

    def ensure(self, slot, n_tokens, queries_from=None):
        self.state.ensure(slot, n_tokens)
        return self.pages.ensure(slot, n_tokens)

    def rollback(self, slot, n_tokens):
        return self.state.rollback(slot, n_tokens)

    def free(self, slot):
        self.state.free(slot)
        return self.pages.free(slot)


def ring_columns(window, page_size, span):
    """Columns of a window layer's table: the most pages that hold a
    key some query of one group of launches still sees. Between two
    fences the queries of a slot cover up to `span` positions (a
    prefill chunk, or the decode steps of a block), the earliest sees
    `window` keys back, and an interval of n positions touches at most
    (n - 2) // page + 2 pages wherever it begins."""
    return (int(window) + int(span) - 3) // int(page_size) + 2


class RingKVCache(PagedKVCache):
    """The pool of layers that attend over a sliding window: query t
    sees keys (t - window, t]. A slot's table is a RING of
    `ring_columns` columns, logical page p in column p % ring; pages
    wholly behind the window of the earliest coming query go back to
    the free list at the fence that passes them (`ensure` with
    `queries_from`), and their columns take the pages ahead. Admission
    reserves the ring, or the request's worst case where that is
    smaller. Released pages keep their data until reassigned; the
    compiled programs never look behind a query's window."""

    def __init__(self, window, span, page_size, **kw):
        self.window = int(window)
        self.ring = ring_columns(window, page_size, span)
        super().__init__(page_size=page_size, max_pages_per_slot=self.ring,
                         **kw)
        self._first = {}       # slot -> logical page of _pages[slot][0]

    def pages_to_reserve(self, n_tokens_worst_case):
        return min(self.ring, self.pages_for_tokens(n_tokens_worst_case))

    def prefill_keys(self, start, n):
        """The walk begins at the page of the first query's first
        visible key."""
        return super().prefill_keys(start, n,
                                    max(start - self.window + 1, 0))

    def reserved_tokens(self, slot):
        """A ring bounds the pages, not the tokens: the full layers'
        pool bounds those."""
        return np.iinfo(np.int64).max if slot in self._reserved else 0

    def released_pages(self):
        """Pages the live slots have given back as their windows
        passed (what whole histories would hold beside `pages_in_use`)."""
        return sum(self._first.values())

    def admit(self, slot, n_tokens_worst_case, name=None):
        super().admit(slot, n_tokens_worst_case, name)
        self._first[slot] = 0

    def ensure(self, slot, n_tokens, queries_from=None):
        """Pages for positions [lo, n_tokens), lo the first key that
        the query at `queries_from` sees (0 where none is given:
        nothing is released). Pages behind lo are released first, so
        their columns are free for the pages ahead."""
        if slot not in self._pages:
            raise ValueError(f"slot {slot} is not admitted")
        pages = self._pages[slot]
        lo = 0 if queries_from is None else \
            max(int(queries_from) - self.window + 1, 0)
        first = self._first[slot]
        passed = min(lo // self.page_size - first, len(pages))
        if passed > 0:
            self._free.extend(reversed(pages[:passed]))
            for p in range(first, first + passed):
                self.tables[slot, p % self.ring] = 0
            del pages[:passed]
            first = self._first[slot] = first + passed
            self.table_version += 1
        need = self.pages_for_tokens(n_tokens) - first
        if need > self.ring:
            raise RuntimeError(
                f"slot {slot}: positions [{first * self.page_size}, "
                f"{n_tokens}) do not fit a ring of {self.ring} pages "
                "(tell ensure() where the coming queries begin)")
        while len(pages) < need:
            phys = self._free.pop()
            self.tables[slot, (first + len(pages)) % self.ring] = phys
            pages.append(phys)
            self.table_version += 1
        return pages

    def rollback(self, slot, n_tokens):
        """As the base's, on the pages still held: what the window
        passed stays released (a rejected suffix lies ahead of it)."""
        if slot not in self._pages:
            raise ValueError(f"slot {slot} is not admitted")
        pages, first = self._pages[slot], self._first[slot]
        need = max(self.pages_for_tokens(n_tokens) - first, 0)
        if need >= len(pages):
            return 0
        freed = pages[need:]
        del pages[need:]
        self._free.extend(reversed(freed))
        for p in range(first + need, first + need + len(freed)):
            self.tables[slot, p % self.ring] = 0
        self.table_version += 1
        return len(freed)

    def free(self, slot):
        self._first.pop(slot, None)
        return super().free(slot)


class WindowedKVCache:
    """Two pools and two tables a slot, for a model whose layers
    attend some over a sliding window and some over everything
    (`models/trinity.py`): `full` (a `PagedKVCache` over the full
    layers, whole histories) and `window` (a `RingKVCache` over the
    window layers) behind the one interface. A request is admitted
    only if the full pool covers its worst case and the window pool
    its ring; both grow at the same fences, and the window half gives
    pages back as the queries move on. Every fence row carries
    `kv_pages_full_in_use`, `kv_pages_window_in_use` and
    `kv_pages_window_released` (what the live slots' window layers
    would hold besides, had they kept whole histories: in_use +
    released = the full pool's in_use)."""

    kind = "paged+window"

    def __init__(self, full, window):
        self.full, self.window = full, window
        self.pool_bytes = full.pool_bytes + window.pool_bytes
        self.num_pages = full.num_pages       # the tracker's snapshot
        self.page_size = full.page_size
        self.max_slots = full.max_slots

    # the tables the engine uploads: the full layers', then the ring
    tables = property(lambda self: (self.full.tables, self.window.tables))
    table_version = property(lambda self: self.full.table_version +
                             self.window.table_version)

    def slots(self):
        return self.full.slots()

    def reserved_tokens(self, slot):
        return self.full.reserved_tokens(slot)

    def allocated_pages(self, slot):
        return self.full.allocated_pages(slot) + \
            self.window.allocated_pages(slot)

    def never_fits(self, n_tokens_worst_case):
        return self.full.never_fits(n_tokens_worst_case) or \
            self.window.never_fits(n_tokens_worst_case)

    def reservation(self, n_tokens_worst_case):
        return {"kv_pages_reserved": int(
            self.full.pages_to_reserve(n_tokens_worst_case)),
            "kv_pages_window_reserved": int(
                self.window.pages_to_reserve(n_tokens_worst_case))}

    def occupancy(self):
        return {"kv_pages_full_in_use": int(self.full.pages_in_use()),
                "kv_pages_window_in_use": int(self.window.pages_in_use()),
                "kv_pages_window_released": int(
                    self.window.released_pages()),
                "kv_pages_free": int(self.full.free_pages())}

    def ledger_occupancy(self):
        full = self.full.ledger_occupancy()
        return {**self.occupancy(),
                "kv_pages_full_in_use": full["kv_pages_in_use"],
                "kv_pages_window_in_use":
                self.window.ledger_occupancy()["kv_pages_in_use"],
                "kv_page_utilization": full["kv_page_utilization"]}

    PREFILL_KEYS = PagedKVCache.PREFILL_KEYS + (
        "kv_prefill_keys_window_attended", "kv_prefill_keys_window_tabled")

    def prefill_keys(self, start, n):
        """The full layers' (attended, tabled), then the window
        layers'."""
        return self.full.prefill_keys(start, n) + \
            self.window.prefill_keys(start, n)

    def attended(self, active, pos, launches=0, advanced=0, prefill_rows=0,
                 prefill_tokens=0, prefill_keys=()):
        """The pages the decode kernel walks at the next launch: every
        page of a live slot in a full layer, the window's in a window
        layer (one count a pool; a pool's layers walk alike). And the
        keys the fence's prefill launches walked against their table
        rows' keys, a full layer's and a window layer's."""
        page = self.page_size
        last = pos[active] // page
        first = np.maximum(pos[active] - self.window.window + 1, 0) // page
        return {"kv_pages_attended": int((last + 1).sum()),
                "kv_pages_window_attended": int((last - first + 1).sum()),
                **{k: int(v) for k, v in zip(self.PREFILL_KEYS,
                                             prefill_keys)}}

    def utilization_counter(self, occupancy):
        """The trace export's one track: the full layers' pool, which
        admission fills first."""
        return "kv_page_utilization", {
            "in_use": occupancy["kv_pages_full_in_use"],
            "free": occupancy["kv_pages_free"]}

    def slot_operand(self, slot):
        """What the prefill program is handed to find `slot`'s cache:
        its row of both tables."""
        return (self.full.tables[slot], self.window.tables[slot])

    def can_admit(self, n_tokens_worst_case):
        return self.full.can_admit(n_tokens_worst_case) and \
            self.window.can_admit(n_tokens_worst_case)

    def admit(self, slot, n_tokens_worst_case, name=None):
        if not self.can_admit(n_tokens_worst_case):
            raise RuntimeError(
                f"kv cache cannot admit {n_tokens_worst_case} tokens: "
                f"{self.full.free_pages()} free pages "
                f"({self.full.reserved_unallocated()} reserved) for the "
                f"full layers, {self.window.free_pages()} "
                f"({self.window.reserved_unallocated()} reserved) for "
                "the window layers")
        self.full.admit(slot, n_tokens_worst_case, name)
        self.window.admit(slot, n_tokens_worst_case, name)

    def ensure(self, slot, n_tokens, queries_from=None):
        pages = self.full.ensure(slot, n_tokens)   # holds the bound
        self.window.ensure(slot, n_tokens, queries_from)
        return pages

    def rollback(self, slot, n_tokens):
        return self.full.rollback(slot, n_tokens) + \
            self.window.rollback(slot, n_tokens)

    def free(self, slot):
        return self.full.free(slot) + self.window.free(slot)


class HybridKVCache:
    """Three kinds of slot state behind the one interface, for a model
    whose layers keep DIFFERENT things (`models/phi4flash.py`): `state`
    (a `RecurrentStateCache` over the Mamba layers), `window` (a
    `RingKVCache` over the layers that attend over a sliding window)
    and `shared` (a `PagedKVCache` of ONE layer, whole histories, which
    its writer and `readers - 1` later layers attend to). A request is
    admitted only if all three have room, grows in the two paged ones
    at the same fences, and holds its part of each until it is freed;
    no part knows of another. Every fence row carries the state's
    counters, the ring's (`kv_pages_window_*`, as the windowed cache
    names them) and the shared pool's: `kv_pages_shared_in_use`,
    `kv_pages_shared_attended` (the pages a decode launch reads of it:
    the live slots' pages x the layers that read them) and
    `prefill_layers_run` (the fence's prefill launches x
    `caching_layers`, the layers such a launch runs). State cannot be
    rewound, so `rollback` is refused."""

    kind = "state+window+shared"

    def __init__(self, state, window, shared, readers, caching_layers,
                 prefill_chunk):
        self.state, self.window, self.shared = state, window, shared
        self.readers, self.caching_layers = int(readers), int(caching_layers)
        self.prefill_chunk = int(prefill_chunk)
        self.pool_bytes = (state.pool_bytes + window.pool_bytes +
                           shared.pool_bytes)
        self.num_pages = shared.num_pages     # the tracker's snapshot
        self.page_size = shared.page_size
        self.max_slots = shared.max_slots

    # the tables the engine uploads: the shared pool's, then the ring
    tables = property(lambda self: (self.shared.tables, self.window.tables))
    table_version = property(lambda self: self.shared.table_version +
                             self.window.table_version)

    def state_shapes(self):
        return self.state.state_shapes()

    def state_dtypes(self):
        return self.state.state_dtypes()

    def slots(self):
        return self.state.slots()

    def reserved_tokens(self, slot):
        return min(self.shared.reserved_tokens(slot),
                   self.state.reserved_tokens(slot))

    def allocated_pages(self, slot):
        return self.shared.allocated_pages(slot) + \
            self.window.allocated_pages(slot)

    def never_fits(self, n_tokens_worst_case):
        return self.shared.never_fits(n_tokens_worst_case) or \
            self.window.never_fits(n_tokens_worst_case) or \
            self.state.never_fits(n_tokens_worst_case)

    def reservation(self, n_tokens_worst_case):
        return {"kv_pages_reserved": int(
            self.shared.pages_to_reserve(n_tokens_worst_case)),
            "kv_pages_window_reserved": int(
                self.window.pages_to_reserve(n_tokens_worst_case)),
            **self.state.reservation(n_tokens_worst_case)}

    def occupancy(self):
        return {"kv_pages_shared_in_use": int(self.shared.pages_in_use()),
                "kv_pages_window_in_use": int(self.window.pages_in_use()),
                "kv_pages_window_released": int(
                    self.window.released_pages()),
                "kv_pages_free": int(self.shared.free_pages()),
                **self.state.occupancy()}

    def ledger_occupancy(self):
        shared = self.shared.ledger_occupancy()
        return {**self.occupancy(),
                "kv_pages_shared_in_use": shared["kv_pages_in_use"],
                "kv_pages_window_in_use":
                self.window.ledger_occupancy()["kv_pages_in_use"],
                "kv_page_utilization": shared["kv_page_utilization"]}

    def prefill_keys(self, start, n):
        """Prefill only WRITES the shared pool, and its rings go
        through `diff_attention`: nothing is counted."""
        return ()

    def attended(self, active, pos, launches=0, advanced=0, prefill_rows=0,
                 prefill_tokens=0, prefill_keys=()):
        """What the next decode launch reads: every page of a live
        slot once a reading layer, the window's pages once a window
        layer; what the fence's launches took through the state; and
        the layers its prefill launches ran."""
        page = self.page_size
        last = pos[active] // page
        first = np.maximum(pos[active] - self.window.window + 1, 0) // page
        return {"kv_pages_shared_attended":
                int((last + 1).sum()) * self.readers,
                "kv_pages_window_attended":
                int((last - first + 1).sum()) * self.window.n_layer,
                "prefill_layers_run": self.caching_layers * (
                    int(prefill_rows) // self.prefill_chunk),
                **self.state.attended(active, pos, launches, advanced,
                                      prefill_rows, prefill_tokens)}

    def utilization_counter(self, occupancy):
        """The trace export's one track: the shared pool, which
        admission fills first."""
        return "kv_page_utilization", {
            "in_use": occupancy["kv_pages_shared_in_use"],
            "free": occupancy["kv_pages_free"]}

    def slot_operand(self, slot):
        """What the prefill program is handed to find `slot`'s cache:
        its row of both tables and its index into the state."""
        return (self.shared.tables[slot], self.window.tables[slot],
                self.state.slot_operand(slot))

    def can_admit(self, n_tokens_worst_case):
        return self.shared.can_admit(n_tokens_worst_case) and \
            self.window.can_admit(n_tokens_worst_case) and \
            self.state.can_admit(n_tokens_worst_case)

    def admit(self, slot, n_tokens_worst_case, name=None):
        if not self.can_admit(n_tokens_worst_case):
            raise RuntimeError(
                f"cache cannot admit {n_tokens_worst_case} tokens: "
                f"{self.shared.free_pages()} free pages "
                f"({self.shared.reserved_unallocated()} reserved) of the "
                f"shared pool, {self.window.free_pages()} "
                f"({self.window.reserved_unallocated()} reserved) of the "
                f"rings, {self.state.free_slots()} free slots of state")
        self.shared.admit(slot, n_tokens_worst_case, name)
        self.window.admit(slot, n_tokens_worst_case, name)
        self.state.admit(slot, n_tokens_worst_case, name)

    def ensure(self, slot, n_tokens, queries_from=None):
        self.state.ensure(slot, n_tokens)
        pages = self.shared.ensure(slot, n_tokens)   # holds the bound
        self.window.ensure(slot, n_tokens, queries_from)
        return pages

    def rollback(self, slot, n_tokens):
        return self.state.rollback(slot, n_tokens)

    def free(self, slot):
        self.state.free(slot)
        return self.shared.free(slot) + self.window.free(slot)
