"""Inference/serving engine tests (ISSUE 12).

Covers:
  * decode-step logits against the float32 training-path forward on
    the same prefix, within a written tolerance (float roundoff: the
    decode kernel sums the same products in another order; ROADMAP
    Design item 2);
  * paged attention (prefill's window path) vs a contiguous-cache
    dense_attention reference;
  * page alloc/free accounting vs independent byte arithmetic, and
    the `kv_cache` ledger category == pool bytes invariant (the PR-9
    ledger window-bound pattern);
  * the NO-HOST-SYNC guard for a multi-request decode loop: zero
    `jax.device_get`/`jax.effects_barrier` between serving fences,
    exactly ONE device_get per fence;
  * continuous-batching scheduler semantics: admission beyond slot
    count, chunked-prefill interleaving, EOS/max-tokens eviction,
    page reuse — with per-request outputs IDENTICAL to isolated
    single-request runs (cache isolation);
  * int8 weight-only quantization within pinned tolerance of fp32;
  * device-side sampling (top_k=1 == greedy; same-seed determinism);
  * `inference` config-block validation and serving monitor events.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import (InferenceConfig, InferenceConfigError,
                                     InferenceEngine, PagedKVCache,
                                     Request, ServingLoop)
from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config


def _params(model):
    return model.init(jax.random.PRNGKey(0),
                      {"input_ids": np.zeros((1, 8), np.int32)})


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_gpt2_config()
    model = GPT2ForCausalLM(cfg)
    params = _params(model)
    engine = InferenceEngine(cfg, params, {"inference": {
        "max_slots": 4, "prefill_chunk": 16, "sync_every": 4,
        "max_new_tokens": 32,
        "kv_cache": {"num_pages": 120, "page_size": 4}}})
    return cfg, model, params, engine


def _train_logits(model, params, tokens):
    out = model.apply(params, np.asarray(tokens, np.int32)[None, :],
                      True)
    return np.asarray(out)[0, -1]


# ----------------------------------------------------------------------
# decode-logits parity vs the training forward
# ----------------------------------------------------------------------
# what float32 roundoff may put between two programs that sum the same
# products in different orders (observed 2e-7 to 5e-7 at these sizes;
# a wrong mask, a stale page or a dropped key is 1e-2 and more)
LOGITS_ATOL = 3e-6


def test_decode_logits_match_float32_training_forward(setup):
    """fp32, total length <= 12: the decode program (the page-walking
    kernel) against the training forward on the same prefix, at every
    generated position, within float32 roundoff, and with the same
    greedy token. Any drift of the serving math from the training
    math shows up here as a hard failure."""
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(1)
    prompt = r.randint(0, cfg.vocab_size, size=7).astype(np.int32)
    engine.start_request(0, prompt, max_new=5)
    cur = list(prompt)
    for step in range(5):
        logits = np.asarray(engine.decode_once()[0])
        ref = _train_logits(model, params, cur)
        np.testing.assert_allclose(logits, ref, atol=LOGITS_ATOL, rtol=0,
                                   err_msg=f"step {step}")
        assert logits.argmax() == ref.argmax()
        cur.append(int(logits.argmax()))
    engine.reset()


def test_decode_logits_roundoff_parity_long(setup):
    """Longer sequences (chunked prefill, length past XLA-CPU's
    small-gemm threshold): the same math through differently-shaped
    programs — parity to float roundoff (observed ~2e-7; pinned at
    3e-6), greedy tokens identical."""
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(2)
    prompt = r.randint(0, cfg.vocab_size, size=37).astype(np.int32)
    engine.start_request(0, prompt, max_new=20)
    cur = list(prompt)
    for _ in range(20):
        logits = np.asarray(engine.decode_once()[0])
        ref = _train_logits(model, params, cur)
        np.testing.assert_allclose(logits, ref, atol=LOGITS_ATOL, rtol=0)
        assert logits.argmax() == ref.argmax()
        cur.append(int(logits.argmax()))
    engine.reset()


# ----------------------------------------------------------------------
# paged cache accounting vs independent byte arithmetic
# ----------------------------------------------------------------------
def test_page_alloc_free_accounting_vs_byte_arithmetic():
    from deepspeed_tpu.monitor.memory import CAT_KV, MemoryLedger
    ledger = MemoryLedger()
    cache = PagedKVCache(n_layer=2, n_head=4, head_dim=16,
                         num_pages=32, page_size=4, max_slots=4,
                         max_pages_per_slot=8, dtype=np.float32,
                         ledger=ledger)
    # independent arithmetic: one page = 2 (K+V) * L * page * row * 4B,
    # the row being H * D = 64 values on the one lane tile they take
    assert cache.lanes == 128 and cache.pool_shape(2) == (2, 32, 4, 128)
    page_bytes = 2 * 2 * 4 * cache.lanes * 4
    assert cache.page_bytes == page_bytes
    assert cache.pool_bytes == 32 * page_bytes

    def kv_total():
        return ledger.totals()["hbm"].get(CAT_KV, 0)

    # empty cache: the whole pool is 'unallocated' but still resident
    assert kv_total() == cache.pool_bytes

    cache.admit(0, 13, name="a")           # worst case ceil(13/4)=4 pages
    assert cache.allocated_pages(0) == 0   # reservation only
    cache.ensure(0, 6)                     # ceil(6/4)=2 pages assigned
    assert cache.allocated_pages(0) == 2
    assert cache.slot_bytes(0) == 2 * page_bytes
    assert kv_total() == cache.pool_bytes  # invariant: total == pool
    cache.ensure(0, 13)
    assert cache.slot_bytes(0) == 4 * page_bytes
    # the cache-side twins of the tracker's ledger-derived utilization
    # (cross-checked in test_kv_page_utilization_ledger_vs_cache_twins)
    assert cache.pages_in_use() == 4
    assert cache.utilization() == 4 / 31
    # per-request ledger entry matches the arithmetic
    tops = {b["name"]: b["bytes"] for b in ledger.top_buffers(16)
            if b["category"] == CAT_KV}
    assert tops["request.s0.a"] == 4 * page_bytes

    # growth past the reservation must refuse, not corrupt
    with pytest.raises(RuntimeError):
        cache.ensure(0, 17)

    # admission control: 31 allocatable pages, 4 held + reservations
    cache.admit(1, 16, name="b")           # reserves 4 more
    assert cache.free_pages() == 31 - 4
    # a request needing more than the uncommitted remainder is refused
    assert not cache.can_admit(4 * (31 - 4 - 4 + 1))
    assert cache.can_admit(8)

    # free returns every page and closes the ledger entry
    freed = cache.free(0)
    assert freed == 4
    assert cache.free_pages() == 31
    tops = {b["name"] for b in ledger.top_buffers(16)
            if b["category"] == CAT_KV}
    assert "request.s0.a" not in tops
    assert kv_total() == cache.pool_bytes
    # the freed pages are reusable immediately
    cache.ensure(1, 16)
    assert cache.slot_bytes(1) == 4 * page_bytes
    cache.free(1)
    assert cache.free_pages() == 31
    assert (cache.tables == 0).all()


def test_serving_kv_ledger_matches_pool_through_lifecycle(setup):
    from deepspeed_tpu.monitor.memory import CAT_KV
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(4)
    prompt = r.randint(0, cfg.vocab_size, size=11).astype(np.int32)
    engine.start_request(0, prompt, max_new=6)
    cats = engine.monitor.ledger.totals()["hbm"]
    assert cats[CAT_KV] == engine.cache.pool_bytes
    # start_request assigns the worst case up front: ceil((11+6)/4)
    assert engine.cache.slot_bytes(0) == \
        -(-(11 + 6) // 4) * engine.cache.page_bytes
    engine.decode_block(6)
    engine.fetch_state()
    engine.reset()
    assert engine.cache.allocated_bytes() == 0
    assert engine.monitor.ledger.totals()["hbm"][CAT_KV] == \
        engine.cache.pool_bytes


def test_oom_hint_names_kv_cache_num_pages():
    from deepspeed_tpu.monitor.memory import oom_hints
    payload = {"hbm": {"categories": {"kv_cache": 10 * 2**30,
                                      "params": 2 * 2**30},
                       "ledger_bytes": 12 * 2**30,
                       "measured_in_use_per_device": 13 * 2**30,
                       "residual_bytes": 1 * 2**30}}
    hints = " ".join(oom_hints(payload))
    assert "inference.kv_cache.num_pages" in hints


# ----------------------------------------------------------------------
# the no-host-sync guard for the multi-request decode loop
# ----------------------------------------------------------------------
class _SyncCounters:
    """Same instrumentation as test_async_dispatch: count the host-sync
    entry points (`jax.device_get`, `jax.effects_barrier`)."""

    def __init__(self, monkeypatch):
        self.device_get = 0
        self.effects_barrier = 0
        real_get, real_barrier = jax.device_get, jax.effects_barrier

        def counting_get(x):
            self.device_get += 1
            return real_get(x)

        def counting_barrier():
            self.effects_barrier += 1
            return real_barrier()

        monkeypatch.setattr(jax, "device_get", counting_get)
        monkeypatch.setattr(jax, "effects_barrier", counting_barrier)


def test_multi_request_decode_loop_has_zero_host_syncs(setup,
                                                       monkeypatch):
    """The serving acceptance guard: with THREE live requests, decode
    blocks dispatched between fences perform ZERO host<->device syncs,
    and the serving fence costs exactly ONE device_get."""
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(5)
    for slot in range(3):
        prompt = r.randint(0, cfg.vocab_size,
                           size=6 + 3 * slot).astype(np.int32)
        engine.start_request(slot, prompt, max_new=20)
    engine.decode_block(4)     # warm the dispatch path
    counters = _SyncCounters(monkeypatch)
    for _ in range(3):
        engine.decode_block(4)
    assert counters.device_get == 0, \
        f"decode loop called jax.device_get {counters.device_get}x"
    assert counters.effects_barrier == 0
    snap = engine.fetch_state()
    assert counters.device_get == 1, \
        "the serving fence must cost exactly ONE device_get"
    assert snap["n_gen"][:3].min() > 0
    engine.reset()


def test_serving_loop_step_syncs_only_at_fence(setup, monkeypatch):
    """ServingLoop.step (admit -> prefill -> decode block -> fence of
    the block before it) performs exactly one device_get per iteration
    (the fence), the first after idle too: it dispatches two blocks
    and fences the first."""
    cfg, model, params, engine = setup
    engine.reset()
    loop = ServingLoop(engine)
    r = np.random.RandomState(6)
    for i in range(3):
        loop.submit(Request(rid=i, tokens=r.randint(
            0, cfg.vocab_size, size=9), max_new_tokens=12))
    import time
    loop._t0 = time.monotonic()
    loop._last_fence_t = loop._now()
    counters = _SyncCounters(monkeypatch)
    loop.step()    # compile/admission settle: two blocks, one fenced
    assert counters.device_get == 1 and engine.blocks_in_flight() == 1
    n = 0
    while (loop.queue or loop.live or loop.prefilling) and n < 50:
        loop.step()
        n += 1
    assert n > 0
    assert counters.device_get == n + 1, (counters.device_get, n)
    assert counters.effects_barrier == 0
    engine.reset()


def _decode_batch_rows(engine):
    """(the loop's `decode_batch` rows from here on, the sink that
    collects them)."""
    import types
    rows = []
    sink = types.SimpleNamespace(emit=lambda event: rows.append(event)
                                 if event["kind"] == "decode_batch" else None)
    engine.monitor.attach_sink(sink)
    return rows, sink


def test_a_fence_returns_with_the_next_block_in_the_queue(monkeypatch):
    """The `device_get` of step k is entered after block k's dispatch
    and reads block k-1: the fence rows' `blocks_in_flight` is 1 in
    steady state, and 0 where the fence read the newest state (a step
    that only prefilled, the first after idle here; the last block of
    a run, fenced with nothing behind it)."""
    cfg = tiny_gpt2_config()
    params = _params(GPT2ForCausalLM(cfg))
    engine = InferenceEngine(cfg, params, {"inference": {
        "max_slots": 2, "prefill_chunk": 16, "sync_every": 4,
        "max_new_tokens": 16,
        "kv_cache": {"num_pages": 40, "page_size": 8}}})
    rows, _ = _decode_batch_rows(engine)
    order = []
    real_block, real_get = engine.decode_block, jax.device_get
    monkeypatch.setattr(engine, "decode_block",
                        lambda n: (order.append("block"), real_block(n))[1])
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (order.append("get"), real_get(x))[1])
    r = np.random.RandomState(38)
    loop = ServingLoop(engine)
    # 20 prompt tokens: two chunks, so the first step only prefills
    done = loop.serve([Request(rid="a", tokens=r.randint(
        0, cfg.vocab_size, size=20), max_new_tokens=16)])
    assert len(done[0].out_tokens) == 16
    # prefill | blocks 1 and 2, fence 1 | block 3, fence 2 | block 4,
    # fence 3 | block 5, fence 4 | fence 5 (a no-op block: the request
    # ended with the fourth)
    assert order == ["get", "block"] + ["block", "get"] * 4 + ["get"]
    assert [r["blocks_in_flight"] for r in rows] == [0, 1, 1, 1, 1, 0]
    assert [r["iterations"] for r in rows] == [0, 4, 4, 4, 4, 4]
    assert [r["window_tokens"] for r in rows] == [0, 4, 4, 4, 4, 0]
    assert engine.blocks_in_flight() == 0


# ----------------------------------------------------------------------
# continuous batching semantics
# ----------------------------------------------------------------------
def test_continuous_batch_matches_isolated_runs(setup):
    """10 requests through 4 slots (forced queueing + page reuse):
    every request's greedy output must be IDENTICAL to serving it
    alone — cache pages are isolated per request and recycling a page
    never leaks another request's KV."""
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(7)
    reqs = [(i, r.randint(0, cfg.vocab_size,
                          size=int(r.randint(3, 30))).astype(np.int32),
             int(r.randint(4, 12))) for i in range(10)]
    loop = ServingLoop(engine)
    res = loop.serve([Request(rid=i, tokens=t.copy(), max_new_tokens=m)
                      for i, t, m in reqs])
    assert len(res) == 10
    batched = {q.rid: q.out_tokens.tolist() for q in res}
    engine.reset()
    for i, t, m in reqs:
        alone = ServingLoop(engine).serve(
            [Request(rid=i, tokens=t.copy(), max_new_tokens=m)])[0]
        assert alone.out_tokens.tolist() == batched[i], i
    # everything came back: pages all free, ledger back to pool-only
    assert engine.cache.free_pages() == engine.cache.num_pages - 1


def test_a_launch_between_two_blocks_of_the_loop_loses_no_row(setup,
                                                               monkeypatch):
    """A caller's `decode_once` between two of the loop's blocks (the
    benchmark reads the live slots' next logits so) advances every
    live slot by a token that the loop's count of positions, kept by
    the fence, does not hold: `ensure_decode_capacity` counts the
    launches since the fence in, so the block after it has its pages
    and the requests end on the tokens of an undisturbed run (a row
    past the pages asked for would go to the scratch page)."""
    cfg, model, params, engine = setup
    r = np.random.RandomState(11)
    reqs = [(i, r.randint(0, cfg.vocab_size, size=n).astype(np.int32), m)
            for i, (n, m) in enumerate([(5, 30), (9, 28), (14, 31)])]
    make = lambda: [Request(rid=i, tokens=t.copy(), max_new_tokens=m)
                    for i, t, m in reqs]
    engine.reset()
    want = {q.rid: q.out_tokens.tolist()
            for q in ServingLoop(engine).serve(make())}
    engine.reset()
    loop = ServingLoop(engine)
    for q in make():
        loop.submit(q)
    import time
    loop._t0 = time.monotonic()
    loop._last_fence_t = loop._now()
    launches, at, real = 0, {}, engine.decode_block
    page = engine.cache.page_size

    def block(n):
        # the rows this block writes lie on pages the slot holds (the
        # served tokens alone need not show a lost key: greedy
        # decoding over random weights is hard to move)
        for slot, first in at.items():
            last = min(first + n, engine.cache.reserved_tokens(slot)) - 1
            assert engine.cache.tables[slot][last // page] != 0, (slot, last)
        at.clear()
        real(n)

    monkeypatch.setattr(engine, "decode_block", block)
    while loop.queue or loop.live or loop.prefilling:
        loop.step()
        if loop.live:
            snap = engine.fetch_state()
            for slot in loop.live:
                engine.ensure_decode_capacity(slot, int(snap["pos"][slot]), 1)
                if snap["active"][slot]:
                    at[slot] = int(snap["pos"][slot]) + 1
            engine.push_tables()
            engine.decode_once()
            launches += 1
    assert launches >= 4
    assert {q.rid: q.out_tokens.tolist() for q in loop.results} == want
    assert engine.cache.free_pages() == engine.cache.num_pages - 1


def test_a_reused_slot_is_not_read_as_its_last_request_left_it(setup,
                                                               monkeypatch):
    """One slot's worth of work at a time: request `b` is activated
    into the slot that `a` left one block earlier, before the snapshot
    that still shows `a`'s end (`n_gen` 8, not active) is fetched. The
    engine lays the activation over it: the loop does not finish `b`
    on `a`'s count, and a reader of `snap["n_gen"][slot]` over
    `loop.live` at `fetch_state`'s return (the benchmark's recorder)
    never sees `a`'s count under `b`'s name."""
    cfg, model, params, _ = setup
    engine = InferenceEngine(cfg, params, {"inference": {
        "max_slots": 1, "prefill_chunk": 16, "sync_every": 4,
        "max_new_tokens": 16,
        "kv_cache": {"num_pages": 40, "page_size": 8}}})
    r = np.random.RandomState(12)
    tokens = {rid: r.randint(0, cfg.vocab_size, size=n).astype(np.int32)
              for rid, n in (("a", 6), ("b", 11))}
    new = {"a": 8, "b": 12}
    make = lambda rid: Request(rid=rid, tokens=tokens[rid].copy(),
                               max_new_tokens=new[rid])
    want = {rid: ServingLoop(engine).serve([make(rid)])[0]
            .out_tokens.tolist() for rid in ("a", "b")}
    engine.reset()
    loop = ServingLoop(engine)
    seen, stale = {"a": [], "b": []}, []
    real_fetch, real_lay = engine.fetch_state, engine._lay_activations_over

    def lay(snap, taken_at):
        before = int(snap["n_gen"][0]), bool(snap["active"][0])
        real_lay(snap, taken_at)
        if (int(snap["n_gen"][0]), bool(snap["active"][0])) != before:
            stale.append(before)

    def fetch():
        snap = real_fetch()
        for slot, req in loop.live.items():
            seen[req.rid].append(int(snap["n_gen"][slot]))
        return snap

    monkeypatch.setattr(engine, "_lay_activations_over", lay)
    monkeypatch.setattr(engine, "fetch_state", fetch)
    done = {q.rid: q for q in loop.serve([make("a"), make("b")])}
    # the snapshot did show the slot as `a` left it, and was laid over
    assert stale == [(8, False)]
    assert seen["a"] == [4, 8]
    assert seen["b"] == [0, 4, 8, 12]
    assert {rid: q.out_tokens.tolist() for rid, q in done.items()} == want
    assert done["b"].finish_reason == "max_tokens"
    assert done["b"].first_token_at > done["a"].finished_at


def test_a_callers_fetch_between_two_steps_gets_the_newest_state(
        setup, monkeypatch):
    """What the benchmark does when its window closes, between two
    steps of the loop: `fetch_state()`, a page for one more row,
    `decode_once()`. The one block unfetched there is the newest
    state, so the launch's logits are those of the token after
    `out_tokens[:n_gen]` of the snapshot it got; the loop's next fence
    then finds its own block the oldest unfetched (`blocks_in_flight`
    0), accounts both blocks and the caller's launch, and the one
    after has a block behind it again. No token and no row is lost:
    the requests end on the tokens of an undisturbed run."""
    cfg, model, params, engine = setup
    r = np.random.RandomState(13)
    reqs = [(i, r.randint(0, cfg.vocab_size, size=n).astype(np.int32), m)
            for i, (n, m) in enumerate([(7, 30), (21, 26), (3, 32)])]
    make = lambda: [Request(rid=i, tokens=t.copy(), max_new_tokens=m)
                    for i, t, m in reqs]
    engine.reset()
    want = {q.rid: q.out_tokens.tolist()
            for q in ServingLoop(engine).serve(make())}
    engine.reset()
    rows, sink = _decode_batch_rows(engine)
    loop = ServingLoop(engine)
    for q in make():
        loop.submit(q)
    import time
    loop._t0 = time.monotonic()
    loop._last_fence_t = loop._now()
    for _ in range(4):
        loop.step()
    assert len(loop.live) == 3 and engine.blocks_in_flight() == 1
    before = len(rows)
    snap = engine.fetch_state()
    assert snap["blocks_in_flight"] == 0 and engine.blocks_in_flight() == 0
    # newer than what the loop has accounted: its block is unfetched
    assert all(snap["n_gen"][s] == loop._last_n_gen[s] + 4
               for s in loop.live)
    for slot in loop.live:
        engine.ensure_decode_capacity(slot, int(snap["pos"][slot]), 1)
    engine.push_tables()
    logits = np.asarray(engine.decode_once())
    for slot, req in loop.live.items():
        so_far = snap["out_tokens"][slot][:int(snap["n_gen"][slot])]
        ref = _train_logits(model, params,
                            np.concatenate([req.tokens, so_far]))
        np.testing.assert_allclose(logits[slot], ref, atol=LOGITS_ATOL,
                                   rtol=0)
    while loop.unfinished():
        loop.step()
    after = rows[before:]
    assert [r["blocks_in_flight"] for r in after[:3]] == [0, 1, 1]
    # the block the caller's fetch took, the caller's launch and the
    # loop's next block, in one row
    assert after[0]["iterations"] == 8 and after[0]["window_tokens"] == 27
    assert {q.rid: q.out_tokens.tolist() for q in loop.results} == want
    assert engine.cache.free_pages() == engine.cache.num_pages - 1
    engine.monitor.sinks.remove(sink)
    engine.reset()


def test_nothing_is_left_unfetched_by_run_drain_or_reset(setup):
    """`run()` ends with nothing unfetched, so does a loop stepped
    until `unfinished()` is False, and `reset()` drops what is: the
    next `fetch_state` reads the fresh state, not a snapshot of the
    state before."""
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(14)
    make = lambda: [Request(rid=i, tokens=r.randint(
        0, cfg.vocab_size, size=5 + i), max_new_tokens=6 + i)
        for i in range(3)]
    ServingLoop(engine).serve(make())
    assert engine.blocks_in_flight() == 0
    loop = ServingLoop(engine)
    for q in make():
        loop.submit(q)
    import time
    loop._t0 = time.monotonic()
    loop._last_fence_t = loop._now()
    steps = 0
    while loop.queue or loop.live or loop.prefilling:
        loop.step()
        steps += 1
    # the block behind the last fence: one more step fences it
    assert engine.blocks_in_flight() == 1 and loop.unfinished()
    assert loop.step() and not loop.unfinished()
    assert engine.blocks_in_flight() == 0 and not loop.step()
    assert len(loop.results) == 3

    engine.start_request(0, r.randint(0, cfg.vocab_size, size=6), max_new=12)
    engine.decode_block(4)
    engine.decode_block(4)
    assert engine.blocks_in_flight() == 2
    # oldest first; a caller that never fetches keeps the newest two
    engine.decode_block(2)
    assert engine.blocks_in_flight() == 2
    assert engine.fetch_state()["n_gen"][0] == 8
    engine.reset()
    assert engine.blocks_in_flight() == 0
    snap = engine.fetch_state()
    assert not snap["active"].any() and not snap["n_gen"].any()
    assert snap["blocks_in_flight"] == 0


def test_chunked_prefill_interleaves_with_decode(setup):
    """A long prompt (3 chunks) admitted while another request decodes:
    the decoding request keeps generating between the chunks (its
    token count advances before the long prompt goes live), and the
    long request's output still matches its isolated run."""
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(8)
    short = r.randint(0, cfg.vocab_size, size=4).astype(np.int32)
    long_p = r.randint(0, cfg.vocab_size, size=40).astype(np.int32)
    loop = ServingLoop(engine)
    loop.submit(Request(rid="short", tokens=short, max_new_tokens=24))
    loop.submit(Request(rid="long", tokens=long_p, max_new_tokens=6))
    import time
    loop._t0 = time.monotonic()
    loop._last_fence_t = loop._now()
    # drive manually: after the first step the short request is live;
    # the long one is still prefilling (40 tokens / 16-chunk > 1 turn)
    loop.step()
    assert "long" in {q.rid for q, _ in loop.prefilling.values()} or \
        any(q.rid == "long" for q in loop.live.values())
    interleaved = False
    for _ in range(60):
        if not (loop.queue or loop.live or loop.prefilling):
            break
        was_prefilling = any(q.rid == "long"
                             for q, _ in loop.prefilling.values())
        short_live = any(q.rid == "short" for q in loop.live.values())
        if was_prefilling and short_live and \
                int(loop._last_n_gen[list(loop.live)[0]]) > 0:
            interleaved = True
        loop.step()
    assert interleaved, \
        "the short request never decoded while the long one prefilled"
    out = {q.rid: q.out_tokens.tolist() for q in loop.results}
    engine.reset()
    ref = ServingLoop(engine).serve(
        [Request(rid="long", tokens=long_p.copy(), max_new_tokens=6)])[0]
    assert out["long"] == ref.out_tokens.tolist()
    engine.reset()


def test_out_of_order_arrivals_do_not_block_ready_requests(setup):
    """A not-yet-arrived request at the queue head must not block an
    already-arrived one behind it (submission order need not be
    arrival order)."""
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(17)
    loop = ServingLoop(engine)
    loop.submit(Request(rid="late", tokens=r.randint(
        0, cfg.vocab_size, size=5), max_new_tokens=4,
        arrival_time=30.0))
    loop.submit(Request(rid="now", tokens=r.randint(
        0, cfg.vocab_size, size=5), max_new_tokens=4,
        arrival_time=0.0))
    import time
    loop._t0 = time.monotonic()
    loop._last_fence_t = loop._now()
    for _ in range(20):
        loop.step()
        if loop.results:
            break
    assert loop.results and loop.results[0].rid == "now", \
        "the ready request starved behind a future arrival"
    # the future request is still queued, untouched
    assert len(loop.queue) == 1 and loop.queue[0].rid == "late"
    engine.reset()


def test_eos_eviction(setup):
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(9)
    prompt = r.randint(0, cfg.vocab_size, size=8).astype(np.int32)
    # learn what greedy generates, then make the FIRST token the EOS
    probe = ServingLoop(engine).serve(
        [Request(rid="p", tokens=prompt.copy(), max_new_tokens=4)])[0]
    assert probe.finish_reason == "max_tokens"
    eos = int(probe.out_tokens[0])
    engine.reset()
    got = ServingLoop(engine).serve(
        [Request(rid="e", tokens=prompt.copy(), max_new_tokens=10,
                 eos_token_id=eos)])[0]
    assert got.finish_reason == "eos"
    # the EOS token is recorded, and generation stopped right there
    assert got.out_tokens.tolist() == [eos]
    engine.reset()


def test_max_tokens_eviction_and_counts(setup):
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(10)
    res = ServingLoop(engine).serve(
        [Request(rid=i, tokens=r.randint(0, cfg.vocab_size, size=5),
                 max_new_tokens=7) for i in range(2)])
    for q in res:
        assert q.finish_reason == "max_tokens"
        assert len(q.out_tokens) == 7
        assert q.finished_at is not None and q.admitted_at is not None
    engine.reset()


# ----------------------------------------------------------------------
# sampling
# ----------------------------------------------------------------------
def test_topk1_sampling_equals_greedy(setup):
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(11)
    prompt = r.randint(0, cfg.vocab_size, size=9).astype(np.int32)
    greedy = ServingLoop(engine).serve(
        [Request(rid="g", tokens=prompt.copy(), max_new_tokens=8)])[0]
    engine.reset()
    topk1 = ServingLoop(engine).serve(
        [Request(rid="t", tokens=prompt.copy(), max_new_tokens=8,
                 temperature=1.0, top_k=1)])[0]
    assert topk1.out_tokens.tolist() == greedy.out_tokens.tolist()
    engine.reset()


def test_sampling_same_seed_is_deterministic(setup):
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(12)
    prompt = r.randint(0, cfg.vocab_size, size=9).astype(np.int32)

    def run():
        engine.reset()
        return ServingLoop(engine).serve(
            [Request(rid="s", tokens=prompt.copy(), max_new_tokens=8,
                     temperature=0.8, top_k=16)])[0].out_tokens.tolist()

    a = run()
    # the decode program's step counter keeps advancing across resets?
    # no: reset() rebuilds state with step=0, so the stream replays
    b = run()
    assert a == b
    assert all(0 <= t < cfg.vocab_size for t in a)
    engine.reset()


# ----------------------------------------------------------------------
# the draw runs only when a live slot asks for it (ISSUE 36)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def drawing():
    """An engine of its own, built while its programs' jaxprs are
    recorded, with a sink that keeps the loop's `decode_batch` rows."""
    import types
    from tests.paged_oracle import traced_programs
    cfg = tiny_gpt2_config()
    params = _params(GPT2ForCausalLM(cfg))
    with traced_programs() as jaxprs:
        engine = InferenceEngine(cfg, params, {"inference": {
            "max_slots": 4, "prefill_chunk": 16, "sync_every": 4,
            "max_new_tokens": 32,
            "kv_cache": {"num_pages": 120, "page_size": 4}}})
    events = []
    engine.monitor.attach_sink(types.SimpleNamespace(emit=events.append))
    return cfg, engine, jaxprs["decode_fn"], events


def _prompts(cfg, seed, n):
    r = np.random.RandomState(seed)
    return [r.randint(0, cfg.vocab_size, size=7 + 2 * i).astype(np.int32)
            for i in range(n)]


def _primitives(jaxpr, but=None):
    """The names of a jaxpr's primitives, inner jaxprs included, the
    equation `but` and what it holds left out."""
    from tests.paged_oracle import equations
    return {eqn.primitive.name for eqn in equations(jaxpr, but)}


def test_top_k_and_the_random_bits_stand_in_one_branch_of_one_cond(
        drawing):
    """The decode function's jaxpr: one `cond` of its own (the decode
    kernel's lie inside the layer scan); `top_k` and the random bits in
    its taken branch and nowhere else, the other branch empty (it
    hands the argmax through)."""
    _, _, jaxpr, _ = drawing
    costly = {"top_k", "random_bits"}
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    skipped, taken = (_primitives(b.jaxpr)
                      for b in conds[0].params["branches"])
    assert not skipped, skipped
    assert costly <= taken
    outside = _primitives(jaxpr, but=conds[0])
    assert "argmax" in outside
    assert not costly & outside, costly & outside


def test_the_draw_branch_draws_what_the_program_drew_before(drawing):
    """A greedy request beside one with temperature 0.8 and top_k 16:
    the greedy one's tokens are those it is served alone, and the
    sampling one's are `process_logits` + `categorical` on the same
    launches' logits under the program's keys (`fold_in(rng, step)`,
    then the slot), as the program computed them for every slot of
    every launch before it asked whether any slot wanted them."""
    from deepspeed_tpu.inference.speculative import process_logits
    cfg, engine, _, _ = drawing
    greedy_prompt, sampled_prompt = _prompts(cfg, 31, 2)
    n, slot = 8, 2
    engine.reset()
    alone = ServingLoop(engine).serve([Request(
        rid="g", tokens=greedy_prompt.copy(), max_new_tokens=n)])[0]
    engine.reset()
    engine.start_request(0, greedy_prompt, max_new=n)
    engine.start_request(slot, sampled_prompt, max_new=n,
                         temperature=0.8, top_k=16)
    # the launch donates the state: what it needs of it, on the host
    rng, top_k, temp = jax.device_get(
        [engine._state[k] for k in ("rng", "top_k", "temperature")])
    cap = min(engine.config.top_k_max, cfg.vocab_size)
    expected, argmaxes = [], []
    for _ in range(n):
        step = int(engine._state["step"])
        logits = engine.decode_once()
        argmaxes.append(int(jnp.argmax(logits[slot])))
        scaled = process_logits(logits.astype(jnp.float32), top_k, temp,
                                cap)
        key = jax.random.fold_in(jax.random.fold_in(rng, step), slot)
        expected.append(int(jax.random.categorical(key, scaled[slot])))
    snap = engine.fetch_state()
    assert snap["out_tokens"][slot][:n].tolist() == expected
    assert snap["out_tokens"][0][:n].tolist() == alone.out_tokens.tolist()
    assert snap["counts"]["decode"]["sample_draw_launches"] == n
    assert expected != argmaxes     # not the argmax by another road
    engine.reset()


def _fence_rows(events):
    return [e for e in events if e["kind"] == "decode_batch"]


def test_an_idle_slot_that_sampled_does_not_switch_the_draw_on(drawing):
    """A sampling request of one block and a greedy one of four: once
    the first has finished, its slot lies idle with its temperature
    still in the state, and the greedy slot's launches draw nothing."""
    cfg, engine, _, events = drawing
    greedy_prompt, sampled_prompt = _prompts(cfg, 32, 2)
    engine.reset()
    del events[:]
    done = ServingLoop(engine).serve([
        Request(rid="g", tokens=greedy_prompt, max_new_tokens=16),
        Request(rid="s", tokens=sampled_prompt, max_new_tokens=4,
                temperature=0.8, top_k=16)])
    assert sorted(len(r.out_tokens) for r in done) == [4, 16]
    rows = _fence_rows(events)
    # the fifth block was in flight when the fence read the fourth's
    # end: it ran every slot as a no-op, and drew nothing either
    assert [r["iterations"] for r in rows] == [4] * 5
    assert [r["window_tokens"] for r in rows] == [8, 4, 4, 4, 0]
    assert [r["sample_draw_launches"] for r in rows] == [4, 0, 0, 0, 0]
    state = jax.device_get({k: engine._state[k]
                            for k in ("temperature", "active")})
    assert state["temperature"][1] > 0 and not state["active"].any()
    engine.reset()


def test_fence_rows_count_the_launches_that_drew(drawing):
    """`sample_draw_launches` on every `decode_batch` row: 0 through a
    greedy run, `iterations` while a sampling slot is live (here the
    first two of four blocks), and the rows sum to the device's own
    count, which a reset clears."""
    cfg, engine, _, events = drawing
    prompts = _prompts(cfg, 33, 3)
    engine.reset()
    del events[:]
    ServingLoop(engine).serve([
        Request(rid=i, tokens=p, max_new_tokens=8 + 4 * i)
        for i, p in enumerate(prompts)])
    rows = _fence_rows(events)
    assert len(rows) == 5         # four blocks and the one behind them
    assert [r["sample_draw_launches"] for r in rows] == [0] * 5
    assert engine.fetch_state()["counts"]["decode"] == {
        "sample_draw_launches": 0}

    engine.reset()
    del events[:]
    ServingLoop(engine).serve([
        Request(rid="g0", tokens=prompts[0], max_new_tokens=16),
        Request(rid="s", tokens=prompts[1], max_new_tokens=8,
                temperature=0.8, top_k=16),
        Request(rid="g1", tokens=prompts[2], max_new_tokens=12)])
    rows = _fence_rows(events)
    assert [r["iterations"] for r in rows] == [4] * 5
    assert [r["sample_draw_launches"] for r in rows] == [4, 4, 0, 0, 0]
    assert engine.fetch_state()["counts"]["decode"][
        "sample_draw_launches"] == 8
    engine.reset()
    assert engine.fetch_state()["counts"]["decode"][
        "sample_draw_launches"] == 0


# ----------------------------------------------------------------------
# int8 weight-only quantization
# ----------------------------------------------------------------------
def test_int8_weight_quant_within_pinned_tolerance(setup):
    """The serving quant A/B (the offload-wire parity convention):
    int8 per-block-scale weights must track the fp32 logits within
    the pinned tolerance on the tiny model (measured ~2e-3) and agree
    on the greedy token."""
    cfg, model, params, engine = setup
    engine.reset()
    e8 = InferenceEngine(cfg, params, {"inference": {
        "max_slots": 4, "prefill_chunk": 16, "sync_every": 4,
        "max_new_tokens": 32, "weight_bits": 8,
        "weight_quant_block": 32,
        "kv_cache": {"num_pages": 120, "page_size": 4}}})
    r = np.random.RandomState(13)
    prompt = r.randint(0, cfg.vocab_size, size=12).astype(np.int32)
    engine.start_request(0, prompt, max_new=6)
    e8.start_request(0, prompt, max_new=6)
    for _ in range(3):
        l32 = np.asarray(engine.decode_once()[0])
        l8 = np.asarray(e8.decode_once()[0])
        assert np.abs(l32 - l8).max() < 2e-2, np.abs(l32 - l8).max()
        assert l32.argmax() == l8.argmax()
    engine.reset()


def test_int8_quant_roundtrip_unit():
    from deepspeed_tpu.ops.transformer.quantized_matmul import (
        int8_matmul, quantize_kernel_int8_np as quantize_kernel_int8)
    r = np.random.RandomState(14)
    w = (r.randn(48, 24) * 0.05).astype(np.float32)
    q, s = quantize_kernel_int8(w, block=16)
    assert q.dtype == np.int8 and q.shape == w.shape
    assert s.shape == (3, 24)
    # dequantised weights within one quantisation step per block
    deq = (q.reshape(3, 16, 24).astype(np.float32) *
           s[:, None, :]).reshape(48, 24)
    assert np.abs(deq - w).max() <= (s.max() / 2) + 1e-8
    x = r.randn(5, 48).astype(np.float32)
    y = np.asarray(int8_matmul(jnp.asarray(x), jnp.asarray(q),
                               jnp.asarray(s), 16, jnp.float32))
    np.testing.assert_allclose(y, x @ deq, atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------------
# config validation + submit validation
# ----------------------------------------------------------------------
def test_inference_config_validation():
    assert InferenceConfig({}).max_slots == 8
    assert InferenceConfig(None).kv_num_pages == 256
    with pytest.raises(InferenceConfigError):
        InferenceConfig({"inference": "nope"})
    with pytest.raises(InferenceConfigError):
        InferenceConfig({"inference": {"max_slots": 0}})
    with pytest.raises(InferenceConfigError):
        InferenceConfig({"inference": {"weight_bits": 4}})
    with pytest.raises(InferenceConfigError):
        InferenceConfig({"inference": {"kv_cache": {"num_pages": 1}}})
    with pytest.raises(InferenceConfigError):
        InferenceConfig({"inference": {"kv_cache": []}})
    with pytest.raises(InferenceConfigError):
        InferenceConfig({"inference": {"sync_every": -1}})


def test_submit_validation(setup):
    cfg, model, params, engine = setup
    engine.reset()
    loop = ServingLoop(engine)
    with pytest.raises(ValueError, match="empty prompt"):
        loop.submit(Request(rid="x", tokens=np.zeros((0,), np.int32)))
    with pytest.raises(ValueError, match="max_seq_len"):
        loop.submit(Request(rid="x", tokens=np.zeros((120,), np.int32),
                            max_new_tokens=30))
    with pytest.raises(ValueError, match="buffer width"):
        loop.submit(Request(rid="x", tokens=np.zeros((4,), np.int32),
                            max_new_tokens=33))
    with pytest.raises(ValueError, match="top_k_max"):
        loop.submit(Request(rid="x", tokens=np.zeros((4,), np.int32),
                            max_new_tokens=4, temperature=1.0,
                            top_k=500))
    with pytest.raises(ValueError, match="top_k_max"):
        engine.start_request(0, np.zeros((4,), np.int32), max_new=4,
                             top_k=500)
    with pytest.raises(ValueError, match="ring width"):
        engine.start_request(0, np.zeros((4,), np.int32), max_new=33)
    # a request that can NEVER fit the page pool is rejected at
    # submit, not left to starve the queue behind it
    small = InferenceEngine(tiny_gpt2_config(), _params(model),
                            {"inference": {
                                "max_slots": 2, "prefill_chunk": 8,
                                "sync_every": 2, "max_new_tokens": 16,
                                "kv_cache": {"num_pages": 4,
                                             "page_size": 4}}})
    with pytest.raises(ValueError, match="usable pages"):
        ServingLoop(small).submit(
            Request(rid="big", tokens=np.zeros((10,), np.int32),
                    max_new_tokens=10))


def test_duplicate_request_ids_keep_ledger_exact(setup):
    """Two live requests sharing one rid must not collide on the
    ledger key: freeing the first leaves the second's entry intact
    and the kv_cache category total stays == pool bytes."""
    from deepspeed_tpu.monitor.memory import CAT_KV
    cfg, model, params, engine = setup
    engine.reset()
    r = np.random.RandomState(16)
    engine.cache.admit(0, 8, name="user-42")
    engine.cache.admit(1, 8, name="user-42")
    engine.cache.ensure(0, 8)
    engine.cache.ensure(1, 8)
    led = engine.monitor.ledger
    assert led.totals()["hbm"][CAT_KV] == engine.cache.pool_bytes
    engine.cache.free(0)
    # slot 1's entry survives slot 0's free
    tops = {b["name"] for b in led.top_buffers(32)
            if b["category"] == CAT_KV}
    assert "request.s1.user-42" in tops
    assert led.totals()["hbm"][CAT_KV] == engine.cache.pool_bytes
    engine.cache.free(1)
    engine.reset()


def test_config_error_names_dotted_key():
    for bad in ({"weight_bits": "eight"}, {"seed": "abc"},
                {"eos_token_id": "x"}):
        with pytest.raises(InferenceConfigError, match="inference\\."):
            InferenceConfig({"inference": bad})


# ----------------------------------------------------------------------
# serving monitor events
# ----------------------------------------------------------------------
def test_serving_monitor_events_schema(tmp_path):
    cfg = tiny_gpt2_config()
    model = GPT2ForCausalLM(cfg)
    params = _params(model)
    engine = InferenceEngine(cfg, params, {
        "inference": {"max_slots": 2, "prefill_chunk": 8,
                      "sync_every": 4, "max_new_tokens": 16,
                      "kv_cache": {"num_pages": 48, "page_size": 4}},
        "monitor": {"enabled": True, "sinks": ["jsonl"],
                    "output_path": str(tmp_path)}})
    r = np.random.RandomState(15)
    ServingLoop(engine).serve(
        [Request(rid=f"r{i}", tokens=r.randint(0, cfg.vocab_size,
                                               size=6 + i),
                 max_new_tokens=5) for i in range(3)])
    engine.monitor.close()
    events = []
    for root, _, files in os.walk(tmp_path):
        for f in files:
            if f.endswith(".jsonl"):
                with open(os.path.join(root, f)) as fh:
                    events += [json.loads(line) for line in fh]
    kinds = {}
    for e in events:
        kinds.setdefault(e["kind"], []).append(e)
    assert len(kinds.get("request_admitted", [])) == 3
    assert len(kinds.get("request_finished", [])) == 3
    assert kinds.get("decode_batch")
    assert kinds.get("memory"), "memory events must ride serving fences"
    adm = kinds["request_admitted"][0]
    for key in ("request_id", "slot", "prompt_tokens", "max_new_tokens",
                "queue_depth", "queued_ms"):
        assert key in adm, key
    fin = kinds["request_finished"][0]
    for key in ("request_id", "slot", "reason", "prompt_tokens",
                "new_tokens", "queued_ms", "ttft_ms", "wall_ms",
                "tokens_per_sec"):
        assert key in fin, key
    dec = kinds["decode_batch"][0]
    for key in ("iterations", "active_slots", "prefilling_slots",
                "queue_depth", "window_tokens", "tokens_per_sec",
                "kv_pages_in_use", "kv_pages_free", "kv_pages_attended",
                "kv_pages_attended_share"):
        assert key in dec, key
    # the memory event's kv_cache category equals the pool bytes
    mem = kinds["memory"][-1]
    assert mem["hbm"]["categories"]["kv_cache"] == \
        engine.cache.pool_bytes


# ----------------------------------------------------------------------
# serving observability (ISSUE 14): lifecycle tracker, SLO events,
# serving timeline, forensics
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def obs_setup(tmp_path_factory):
    """A monitor-enabled engine (tracker + trace export + jsonl) that
    served one 3-request batch; the exported trace snapshot covers
    exactly that batch."""
    tmp = tmp_path_factory.mktemp("serving_obs")
    cfg = tiny_gpt2_config()
    model = GPT2ForCausalLM(cfg)
    params = _params(model)
    engine = InferenceEngine(cfg, params, {
        "inference": {"max_slots": 4, "prefill_chunk": 8,
                      "sync_every": 4, "max_new_tokens": 16,
                      "kv_cache": {"num_pages": 64, "page_size": 4}},
        "monitor": {"enabled": True, "sinks": ["jsonl"],
                    "output_path": str(tmp),
                    "trace": {"enabled": True}}})
    assert engine.tracker is not None
    r = np.random.RandomState(21)
    results = ServingLoop(engine).serve(
        [Request(rid=f"r{i}",
                 tokens=r.randint(0, cfg.vocab_size, size=5 + 7 * i),
                 max_new_tokens=4 + i) for i in range(3)])
    trace_path = engine.monitor.export_trace()
    # snapshot the event log NOW: later tests drive more serving on
    # the same engine, and the schema assertions below are about THIS
    # batch's totals
    events = _jsonl_events(str(tmp))
    return cfg, engine, results, events, trace_path


def _jsonl_events(root):
    events = []
    for dirpath, _, files in os.walk(root):
        for f in files:
            if f.endswith(".jsonl"):
                with open(os.path.join(dirpath, f)) as fh:
                    events += [json.loads(line) for line in fh]
    return events


def test_tracker_absent_without_monitor(setup):
    """No monitor block -> no tracker (the monitor.flight convention);
    every earlier test in this file runs that way and stays valid."""
    cfg, model, params, engine = setup
    assert engine.monitor.enabled is False
    assert engine.tracker is None


def test_observability_config_validation():
    cfg = InferenceConfig({})
    assert cfg.observability_enabled is True
    assert cfg.slo_ttft_ms == 0.0 and cfg.slo_token_ms == 0.0
    off = InferenceConfig({"inference": {
        "observability": {"enabled": False, "slo_ttft_ms": 250,
                          "slo_token_ms": 20}}})
    assert off.observability_enabled is False
    assert off.slo_ttft_ms == 250.0 and off.slo_token_ms == 20.0
    with pytest.raises(InferenceConfigError, match="observability"):
        InferenceConfig({"inference": {"observability": []}})
    with pytest.raises(InferenceConfigError, match="slo_ttft_ms"):
        InferenceConfig({"inference": {
            "observability": {"slo_ttft_ms": -1}}})
    with pytest.raises(InferenceConfigError, match="slo_token_ms"):
        InferenceConfig({"inference": {
            "observability": {"slo_token_ms": "fast"}}})


def test_latency_histogram_fixed_edges_and_percentiles():
    from deepspeed_tpu.monitor.serving import (HIST_EDGES_MS,
                                               LatencyHistogram)
    # the schema-stability contract: edges are a fixed constant, log
    # spaced at 2^(1/3), and the payload width matches
    assert len(HIST_EDGES_MS) == 61
    for a, b in zip(HIST_EDGES_MS, HIST_EDGES_MS[1:]):
        assert 1.2 < b / a < 1.3
    h = LatencyHistogram()
    assert h.percentile(0.5) is None
    h.record(1.0, count=50)
    h.record(100.0, count=50)
    # bucket resolution: one factor-2^(1/3) bucket of the exact value
    assert 1.0 / 1.3 < h.percentile(0.25) / 1.0 < 1.3
    assert 1.0 / 1.3 < h.percentile(0.99) / 100.0 < 1.3
    # out-of-range values clamp into the end buckets, never lost
    h.record(1e-9)
    h.record(1e9)
    ev = h.to_event()
    assert ev["count"] == 102
    assert len(ev["counts"]) == len(HIST_EDGES_MS)
    assert ev["counts"][0] >= 1 and ev["counts"][-1] >= 1
    for key in ("v", "unit", "count", "sum_ms", "counts"):
        assert key in ev, key


def test_sync_guards_with_observability_enabled(obs_setup, monkeypatch):
    """The ISSUE-12 sync contract re-pinned with serving observability
    ENABLED: decode blocks between fences stay at ZERO host syncs and
    the fence costs exactly ONE device_get — the tracker is host
    arithmetic only."""
    import time
    cfg, engine, _, _, _ = obs_setup
    engine.reset()
    r = np.random.RandomState(22)
    loop = ServingLoop(engine)
    for i in range(3):
        loop.submit(Request(rid=f"g{i}", tokens=r.randint(
            0, cfg.vocab_size, size=6 + 2 * i), max_new_tokens=8))
    loop._t0 = time.monotonic()
    loop._last_fence_t = loop._now()
    counters = _SyncCounters(monkeypatch)
    loop.step()    # admission/compile settle: two blocks, one fenced
    assert counters.device_get == 1 and engine.blocks_in_flight() == 1
    n = 0
    while (loop.queue or loop.live or loop.prefilling) and n < 50:
        loop.step()
        n += 1
    assert n > 0
    assert counters.device_get == n + 1, (counters.device_get, n)
    assert counters.effects_barrier == 0
    # engine-level: a decode block dispatches with zero syncs even
    # with the tracker attached
    engine.reset()
    engine.start_request(0, r.randint(0, cfg.vocab_size, size=6),
                         max_new=12)
    engine.decode_block(4)
    counters = _SyncCounters(monkeypatch)
    engine.decode_block(4)
    assert counters.device_get == 0
    assert counters.effects_barrier == 0
    engine.fetch_state()
    assert counters.device_get == 1
    engine.reset()


def test_tracker_percentiles_agree_with_the_requests_own_stamps():
    """The tracker's streaming histograms against the latencies the
    scheduler stamps on each `Request` (another code path, another
    chain of clock readings of the same fences): p50 and p99 of the
    time to first token and of the time a token, within one bucket of
    the fixed edges (2^(1/3) wide; held at 1.45 for what lies between
    the two readings of one fence)."""
    cfg = tiny_gpt2_config()
    engine = InferenceEngine(cfg, _params(GPT2ForCausalLM(cfg)), {
        "inference": {"max_slots": 4, "prefill_chunk": 8,
                      "sync_every": 4, "max_new_tokens": 16,
                      "kv_cache": {"num_pages": 64, "page_size": 4}},
        "monitor": {"enabled": True, "sinks": []}})
    trk = engine.tracker
    r = np.random.RandomState(5)
    results = ServingLoop(engine).serve(
        [Request(rid=i, tokens=r.randint(0, cfg.vocab_size,
                                         size=int(r.randint(4, 29))),
                 max_new_tokens=int(r.randint(6, 15)),
                 arrival_time=0.004 * i) for i in range(12)])
    assert len(results) == 12
    ttft = sorted((q.first_token_at - q.arrival_time) * 1e3
                  for q in results)
    token = []
    for q in results:
        n = max(len(q.out_tokens), 1)
        live = q.live_at if q.live_at is not None else q.admitted_at
        token += [(q.finished_at - live) * 1e3 / n] * n
    token.sort()
    assert trk.hist_ttft_ms.to_event()["count"] == len(ttft)
    assert trk.hist_token_ms.to_event()["count"] == len(token)

    def pick(vals, p):
        return vals[min(int(p * len(vals)), len(vals) - 1)]

    for hist, exact in ((trk.hist_ttft_ms, ttft),
                        (trk.hist_token_ms, token)):
        for p in (0.50, 0.99):
            assert 1 / 1.45 <= hist.percentile(p) / pick(exact, p) \
                <= 1.45, (p, hist.percentile(p), pick(exact, p))
    engine.monitor.close()


def test_serving_slo_jsonl_schema_roundtrip(obs_setup):
    """The new event schema through the real sink: `serving_slo` with
    schema-stable histogram payloads, and the extended timing keys on
    the existing serving events."""
    from deepspeed_tpu.monitor.serving import HIST_EDGES_MS
    cfg, engine, results, events, _ = obs_setup
    kinds = {}
    for e in events:
        kinds.setdefault(e["kind"], []).append(e)
    assert kinds.get("serving_slo"), "serving_slo must ride every fence"
    slo = kinds["serving_slo"][-1]
    for key in ("window_ms", "window_tokens", "tokens_per_sec",
                "active_slots", "prefilling_slots", "queue_depth",
                "kv_pages_in_use", "kv_pages_free",
                "kv_page_utilization", "kv_pages_attended",
                "kv_pages_attended_share", "queue_wait_share",
                "ttft_ms", "token_ms", "queue_ms",
                "ttft_p50_ms", "ttft_p99_ms", "token_p50_ms",
                "token_p99_ms", "queue_p50_ms", "queue_p99_ms",
                "finished_eos", "finished_max_tokens",
                "rejected_submit", "admission_deferred",
                "total_tokens", "goodput_tokens", "goodput_fraction"):
        assert key in slo, key
    # the histogram payload is fixed-width (schema-stable): readers
    # can diff bucket-for-bucket across runs
    for hist_key in ("ttft_ms", "token_ms", "queue_ms"):
        hist = slo[hist_key]
        assert len(hist["counts"]) == len(HIST_EDGES_MS)
        assert hist["count"] == sum(hist["counts"])
    # after all three finished: counts + goodput add up
    assert slo["finished_eos"] + slo["finished_max_tokens"] >= 3
    assert slo["total_tokens"] == sum(len(q.out_tokens)
                                      for q in results)
    assert slo["goodput_fraction"] == 1.0   # no SLO targets set
    assert slo["ttft_ms"]["count"] >= 3
    assert slo["token_p99_ms"] >= slo["token_p50_ms"]
    # extended rows on the PR-12 events
    adm = kinds["request_admitted"][0]
    assert adm["kv_pages_reserved"] > 0
    fin = kinds["request_finished"][0]
    for key in ("prefill_ms", "decode_ms", "token_ms"):
        assert key in fin, key
    assert fin["decode_ms"] > 0 and fin["token_ms"] > 0
    assert "window_ms" in kinds["decode_batch"][0]


def test_serving_trace_exports_slot_timeline(obs_setup):
    """The acceptance trace: passes the existing Chrome-trace
    validator, carries >= 1 per-slot request track with the distinct
    slice types, the serving counter tracks, and per-request finish
    instants the summary recomputes from."""
    from test_trace_export import validate_chrome_trace
    from deepspeed_tpu.monitor.trace_export import (
        CAT_SERVE_DECODE, CAT_SERVE_PREFILL, CAT_SERVE_QUEUE,
        CAT_SERVE_REQUEST, load_trace, summarize_trace)
    cfg, engine, results, _events, trace_path = obs_setup
    doc = load_trace(trace_path)
    validate_chrome_trace(doc)
    tracks = {ev["args"]["name"] for ev in doc["traceEvents"]
              if ev["ph"] == "M"}
    assert any(t.startswith("serve/slot") for t in tracks), tracks
    cats = {ev.get("cat") for ev in doc["traceEvents"]}
    for cat in (CAT_SERVE_QUEUE, CAT_SERVE_PREFILL, CAT_SERVE_DECODE,
                CAT_SERVE_REQUEST):
        assert cat in cats, cat
    counter_names = {ev["name"] for ev in doc["traceEvents"]
                     if ev["ph"] == "C"}
    for name in ("queue_depth", "batch_occupancy",
                 "kv_page_utilization", "tokens_per_sec"):
        assert name in counter_names, name
    s = summarize_trace(doc)
    serving = s.get("serving")
    assert serving and serving["requests"] == 3
    assert serving["new_tokens"] == sum(len(q.out_tokens)
                                        for q in results)
    for key in ("queued_ms", "ttft_ms", "token_ms"):
        assert serving[key]["p50"] is not None
        assert serving[key]["p99"] >= serving[key]["p50"]
    assert serving["goodput_fraction"] == 1.0
    # fidelity: summary TTFT p50 within one histogram... no — the
    # summary is exact (recomputed from instants); compare against the
    # scheduler's independent Request stamps instead
    exact = sorted((q.first_token_at - q.arrival_time) * 1e3
                   for q in results)
    assert abs(serving["ttft_ms"]["p50"] - exact[1]) < \
        max(2.0, 0.5 * exact[1])


def test_ds_trace_summary_serving_cli(obs_setup, capsys, tmp_path):
    from deepspeed_tpu.monitor import trace_cli
    cfg, engine, results, _events, trace_path = obs_setup
    assert trace_cli.main(["summary", "--serving", trace_path]) == 0
    out = capsys.readouterr().out
    assert "serving (per-request" in out
    assert "ttft" in out and "token" in out and "queue_wait" in out
    assert "p50_ms" in out and "p99_ms" in out
    # plain summary also prints the serving section when present
    assert trace_cli.main(["summary", trace_path]) == 0
    assert "serving (per-request" in capsys.readouterr().out
    # a serving-less trace reports so (exit 1)
    from deepspeed_tpu.monitor.trace_export import TraceExporter
    ex = TraceExporter()
    ex.complete("t", "e", 1.0, 0.1)
    plain = str(tmp_path / "plain.json")
    ex.write(plain)
    assert trace_cli.main(["summary", "--serving", plain]) == 1
    assert "no serving events" in capsys.readouterr().out


def test_serving_oom_hints_ranking():
    """The serving-aware hint ranking: kv_cache pages vs max_slots vs
    prefill_chunk, ordered by what dominates."""
    from deepspeed_tpu.monitor.serving import serving_oom_hints
    # pool dominates but mostly unallocated -> num_pages first
    payload = {"hbm": {"categories": {"kv_cache": 10 * 2**30,
                                      "params": 2 * 2**30},
                       "ledger_bytes": 12 * 2**30,
                       "measured_in_use_per_device": 13 * 2**30,
                       "residual_bytes": 1 * 2**30}}
    hints = serving_oom_hints(payload, {
        "kv_page_utilization": 0.1, "requests": []})
    assert hints and "inference.kv_cache.num_pages" in hints[0]
    # pool saturated by reservations -> max_slots first
    hints = serving_oom_hints(payload, {
        "kv_page_utilization": 0.95,
        "requests": [{"phase": "decode"}] * 8})
    assert hints and "inference.max_slots" in hints[0]
    # prefill activations dominate the residual -> prefill_chunk named
    payload_resid = {"hbm": {"categories": {"kv_cache": 1 * 2**30},
                             "ledger_bytes": 8 * 2**30,
                             "measured_in_use_per_device": 10 * 2**30,
                             "residual_bytes": 7 * 2**30}}
    hints = serving_oom_hints(payload_resid, {
        "kv_page_utilization": 0.4,
        "requests": [{"phase": "prefill"}]})
    assert any("inference.prefill_chunk" in h for h in hints)
    # no serving signal -> no serving hints (generic oom_hints remain)
    assert serving_oom_hints({}, {}) == []


def test_crash_during_serving_dumps_live_request_table(tmp_path):
    """Subprocess crash-during-serving: an OOM-shaped failure at a
    serving fence must leave a flight dump whose sticky context (and
    crash extra) names exactly the requests that were in flight, with
    the serving-aware OOM hints ranked in."""
    import subprocess
    import sys
    out_dir = str(tmp_path / "mon")
    script = f"""
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
from deepspeed_tpu.inference import InferenceEngine, Request, ServingLoop
from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config

cfg = tiny_gpt2_config()
model = GPT2ForCausalLM(cfg)
params = model.init(jax.random.PRNGKey(0),
                    {{"input_ids": np.zeros((1, 8), np.int32)}})
engine = InferenceEngine(cfg, params, {{
    "inference": {{"max_slots": 2, "prefill_chunk": 8, "sync_every": 4,
                   "max_new_tokens": 16,
                   "kv_cache": {{"num_pages": 256, "page_size": 4}}}},
    "monitor": {{"enabled": True, "sinks": ["jsonl"],
                 "output_path": {out_dir!r}}}}})
loop = ServingLoop(engine)
r = np.random.RandomState(0)
for i in range(3):
    loop.submit(Request(rid=f"inflight{{i}}",
                        tokens=r.randint(0, cfg.vocab_size, size=7),
                        max_new_tokens=12))
real = engine.fetch_state
calls = {{"n": 0}}
def oom_fence():
    calls["n"] += 1
    if calls["n"] >= 3:
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: out of memory allocating kv pages")
    return real()
engine.fetch_state = oom_fence
loop.run()
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode != 0      # the crash still propagated
    assert "RESOURCE_EXHAUSTED" in proc.stderr
    from deepspeed_tpu.monitor.flight import list_flight_dumps
    dumps = list_flight_dumps(out_dir)
    assert dumps, (proc.stdout[-1000:], proc.stderr[-1000:])
    # the crash guard's "oom" dump (the armed tracker also leaves an
    # atexit dump when the crashed process exits — both are correct)
    docs = []
    for p in dumps:
        with open(p) as f:
            docs.append(json.load(f))
    ooms = [d for d in docs if d["reason"] == "oom"]
    assert ooms, [d["reason"] for d in docs]
    doc = ooms[-1]
    # the live request table: sticky context AND the crash extra
    for table in (doc["context"]["serving"],
                  doc["extra"]["serving"]):
        rows = table["requests"]
        assert rows, table
        for row in rows:
            assert row["request_id"].startswith("inflight")
            for key in ("slot", "phase", "tokens_emitted",
                        "pages_held"):
                assert key in row, key
    # the serving-aware hint ranking rode the oom extra: the pool is
    # 256 pages for 3 tiny requests -> underutilized -> num_pages
    hints = " ".join(doc["extra"]["oom"]["hints"])
    assert "inference.kv_cache.num_pages" in hints


def test_fence_rows_report_the_pages_the_decode_kernel_walks(obs_setup):
    """`kv_pages_attended` on every `decode_batch` and `serving_slo`
    row: ceil((pos + 1) / page) summed over the slots still live at the
    fence, host arithmetic on what the fence fetched, and its share of
    max_slots x max_pages_per_slot (the window the gathered path
    attended to whatever was live)."""
    cfg, engine, _, events, _ = obs_setup
    cache = engine.cache
    whole = cache.max_slots * cache.max_pages_per_slot
    active = np.asarray([True, False, True, True])
    pos = np.asarray([0, 50, 3, 4])        # pages of 4: 1 + 1 + 2
    assert cache.attended(active, pos) == {
        "kv_pages_attended": 4,
        "kv_pages_attended_share": round(4 / whole, 4)}
    assert cache.attended(~active, pos)["kv_pages_attended"] == 13
    rows = {kind: [e for e in events if e["kind"] == kind]
            for kind in ("decode_batch", "serving_slo")}
    assert len(rows["decode_batch"]) == len(rows["serving_slo"]) > 0
    for dec, slo in zip(rows["decode_batch"], rows["serving_slo"]):
        assert dec["kv_pages_attended"] == slo["kv_pages_attended"]
        assert 0 <= dec["kv_pages_attended"] <= dec["kv_pages_in_use"]
        assert dec["kv_pages_attended_share"] == \
            slo["kv_pages_attended_share"] == \
            round(dec["kv_pages_attended"] / whole, 4)
    assert any(e["kv_pages_attended"] for e in rows["decode_batch"])


def test_kv_page_utilization_ledger_vs_cache_twins(obs_setup):
    """The tracker reports KV-page utilization as derived from the
    memory ledger's `kv_cache` category (the cache's
    `ledger_occupancy`); the cache derives it from its own page
    tables (pages_in_use/utilization).
    Two independent accounting chains — they must agree
    page-for-page."""
    cfg, engine, _, _, _ = obs_setup
    engine.reset()
    cache = engine.cache

    def ledger_pages():
        row = cache.ledger_occupancy()
        return (row["kv_pages_in_use"], row["kv_pages_free"],
                row["kv_page_utilization"])

    assert ledger_pages() == (0, cache.num_pages - 1, 0.0)
    assert cache.pages_in_use() == 0 and cache.utilization() == 0.0
    cache.admit(0, 12, name="twin")
    cache.ensure(0, 12)
    in_use, free, util = ledger_pages()
    assert in_use == cache.pages_in_use() > 0
    assert free == (cache.num_pages - 1) - in_use
    assert util == pytest.approx(cache.utilization(), abs=5e-5)  # rounded
    cache.free(0)
    engine.reset()


# ----------------------------------------------------------------------
# the page pools ride in the layer scan's carry (ISSUE 25)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def carried():
    """An engine with speculation on (so: all five serving programs),
    built while its programs' jaxprs are recorded. 3 layers, 2 of them
    the draft's; the pool is large beside everything else a step
    holds, so that one layer's pool among the temporaries shows."""
    from deepspeed_tpu.monitor import programs
    from tests.paged_oracle import traced_programs
    cfg = tiny_gpt2_config(n_layer=3)
    params = _params(GPT2ForCausalLM(cfg))
    with traced_programs() as jaxprs:
        engine = InferenceEngine(cfg, params, {"inference": {
            "max_slots": 4, "prefill_chunk": 16, "sync_every": 4,
            "max_new_tokens": 32,
            "kv_cache": {"num_pages": 600, "page_size": 4},
            "speculative": {"enabled": True, "draft_model": "truncate:2",
                            "k": 3}}})
    temp = {name: programs.memory("jit_" + name)["temp"]
            for name in ("decode_fn", "prefill_fn")}
    return cfg, params, engine, jaxprs, temp


@pytest.mark.parametrize("program", [
    "decode_fn", "prefill_fn", "draft_fn", "verify_fn",
    "draft_prefill_fn"])
def test_pools_are_scan_carry_and_never_a_temporary(carried, program):
    """(a) In the program's jaxpr both pools are carry of the layer
    scan and nothing of a pool's shape (whole, or one layer's) is an
    `xs`, a `ys` or a loop constant: a pool that goes in as `xs` and
    comes back as `ys` is sliced, re-laid and stacked back per layer.
    (b) The compiled decode and prefill programs hold less than ONE
    layer's pool in temporaries (the parent held two whole pools)."""
    from tests.paged_oracle import pools_in_scans
    cfg, _, engine, jaxprs, temp = carried
    pool = engine._state["k_pool"]
    draft_pool = engine._spec_state["dk_pool"]
    assert pool.shape == (3, 600, 4, engine.cache.lanes)
    assert draft_pool.shape == (2,) + pool.shape[1:]
    carried_pools, elsewhere = pools_in_scans(
        jaxprs[program], {pool.shape, draft_pool.shape})
    assert carried_pools == 2, carried_pools
    assert elsewhere == []
    if program in temp:
        assert temp[program] < pool.nbytes // cfg.n_layer, temp


@pytest.mark.parametrize("program", ["prefill_fn", "decode_fn"])
def test_programs_bitexact_vs_per_layer_pool_oracle(carried, program):
    """Prompts of several chunks into two of four slots, then several
    decode steps, against the oracle that loops over the layers in
    Python with one pool a layer: logits and BOTH
    pools equal bit for bit after every launch, scratch page 0 (the
    inactive slots' and the pad rows' writes) included."""
    from tests.paged_oracle import (assert_pools_equal, oracle_forward,
                                    prefill_inputs)
    cfg, params, engine, _, _ = carried
    pools = ("k_pool", "v_pool")
    engine.reset()
    r = np.random.RandomState(7)
    page, chunk = engine.cache.page_size, engine.config.prefill_chunk
    qb = engine.config.weight_quant_block
    k_ref = np.asarray(engine._state["k_pool"])
    v_ref = np.asarray(engine._state["v_pool"])
    for slot, length in ((2, 38), (0, 21)):       # 3 chunks, 2 chunks
        prompt = r.randint(0, cfg.vocab_size, size=length)
        engine.cache.admit(slot, length + 8)
        engine.cache.ensure(slot, length + 8)
        engine.push_tables()
        for start in range(0, length - 1, chunk):
            toks = prompt[start:min(start + chunk, length - 1)]
            engine.prefill_chunk(slot, toks, start)
            if program != "prefill_fn":
                continue
            _, k_ref, v_ref = oracle_forward(
                cfg, params, k_pool=k_ref, v_pool=v_ref, page_size=page,
                quant_block=qb, **prefill_inputs(
                    chunk, toks, start, engine.cache.tables[slot]))
            assert_pools_equal(engine._state, pools, (k_ref, v_ref),
                               (slot, start))
        engine.activate_slot(slot, prompt[-1], length - 1, 8, 0.0, 0,
                             None)
    if program == "prefill_fn":
        assert k_ref[:, 0].any() and k_ref[:, 1:].any()
        engine.reset()
        return
    k_ref = np.asarray(engine._state["k_pool"])
    v_ref = np.asarray(engine._state["v_pool"])
    for step in range(5):
        st = jax.device_get({k: engine._state[k] for k in (
            "cur_token", "pos", "active", "tables")})
        assert list(st["active"]) == [True, False, True, False]
        logits = np.asarray(engine.decode_once())
        ref, k_ref, v_ref = oracle_forward(
            cfg, params, st["cur_token"][:, None], st["pos"][:, None],
            st["active"][:, None], st["pos"], st["tables"], k_ref, v_ref,
            page, qb)
        assert np.array_equal(logits, ref[:, 0]), \
            (step, np.abs(logits - ref[:, 0]).max())
        assert_pools_equal(engine._state, pools, (k_ref, v_ref), step)
    engine.reset()
