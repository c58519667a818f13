"""A prefill chunk's attention over the keys its slot holds
(`ops/transformer/paged_prefill_attention.py`, ISSUE 42) against the
plain form it replaced, which lives on in `tests/paged_oracle.py`
(`dense_prefill_attention`: the slot's whole table row gathered, keys
and values repeated to the query head count, one dense masked softmax).

A case is ONE slot and one chunk: `t` query rows at positions
`start .. start + t - 1` of which the first `n` are a prompt's (the
rest are pad rows, whose result nobody reads), the slot's keys in
pages behind a scrambled table (a straight one, or a ring of a
window's pages). Every row of the pools that holds no visible key
(scratch page 0, pages nobody holds, the rest of the last page, a
ring's released pages) holds finite garbage of large magnitude as a
key and infinity as a value, and must contribute exactly nothing.

The tables here are a few dozen keys wide, so the cases that walk
several blocks set the module's `BLOCK_KEYS` to a few pages; one case
runs the block as the module has it.

Tolerances: float32 against float32, the same products summed block
by block where the plain form sums them at once: 1e-5 (seen: 5e-7).
bfloat16: the probabilities are rounded to the pools' type before the
product with V in both forms, the plain form after it has normalised
them, this one before: 2e-2 on values of size about 1.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kv_cache import ring_columns
from deepspeed_tpu.ops.transformer import latent_attention
from deepspeed_tpu.ops.transformer import paged_prefill_attention as ppa
from deepspeed_tpu.ops.transformer.paged_decode_attention import \
    padded_lanes
from paged_oracle import dense_prefill_attention, equations

ATOL = {"float32": 1e-5, "bfloat16": 2e-2}
GARBAGE = 3e4


def chunk_case(h, hk, d, page, columns, t, start, n, seed, window=0,
               dtype=jnp.float32, garbage=True, ringed=True):
    """(q, k_pool, v_pool, li, tables, q_pos, kv_limit, first, ring):
    the operands of one chunk. `window` > 0: a query sees `window` keys
    back, and (`ringed`) the table is a ring of `columns` columns that
    holds the pages from the first query's first visible key on."""
    rng = np.random.default_rng(seed)
    c = hk * d
    lanes = padded_lanes(c)
    live = start + n
    ringed = ringed and window > 0
    lo = max(start - window + 1, 0) if ringed else 0
    n_pages, li = 2 * columns + 3, 1
    shape = (2, n_pages, page, lanes)
    if garbage:
        other = np.random.default_rng(seed + 1000)
        sign = other.choice([-1.0, 1.0], size=shape)
        k_pool = (sign * GARBAGE * (1 + other.random(shape))) \
            .astype(np.float32)
        v_pool = np.full(shape, np.inf, np.float32)
    else:
        k_pool, v_pool = np.zeros(shape, np.float32), \
            np.zeros(shape, np.float32)
    # a scrambled table; the columns that hold no page of the walk
    # name pages of garbage (a ring's: pages that lie ahead)
    tables = rng.permutation(np.arange(1, n_pages))[:columns][None] \
        .astype(np.int32)
    rows = rng.normal(size=(2, live, c)).astype(np.float32)
    for pos in range(lo, live):
        col = pos // page % columns if ringed else pos // page
        k_pool[li, tables[0, col], pos % page, :c] = rows[0, pos]
        v_pool[li, tables[0, col], pos % page, :c] = rows[1, pos]
    q = jnp.asarray(rng.normal(size=(1, t, h * d)), dtype)
    q_pos = (start + np.arange(t))[None].astype(np.int32)
    first = np.maximum(q_pos - window + 1, 0) if window else None
    return (q, jnp.asarray(k_pool, dtype), jnp.asarray(v_pool, dtype), li,
            tables, q_pos, np.asarray([live - 1], np.int32), first,
            columns if ringed else 0)


def launch(entry, q, k_pool, v_pool, li, tables, q_pos, kv_limit, h, hk,
           first=None, ring=0):
    """One jitted call, traced anew (the block is read at trace time)."""
    return jax.jit(functools.partial(entry, n_head=h, n_kv_head=hk,
                                     ring=ring))(
        q, k_pool, v_pool, li, tables, q_pos, kv_limit, first=first)


def both(case, h, hk, n):
    """(the entry's, the plain form's) result on a case's `n` rows of
    a prompt, in float32."""
    *operands, first, ring = case
    got, want = (np.asarray(launch(entry, *operands, h, hk, first=first,
                                   ring=ring).astype(jnp.float32))[:, :n]
                 for entry in (ppa.paged_prefill_attention,
                               dense_prefill_attention))
    assert np.isfinite(got).all()
    return got, want


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 64 keys, so that tables of a few hundred keys are
    walked in several."""
    monkeypatch.setattr(ppa, "BLOCK_KEYS", 64)
    return 64


# a live length -> (start, n) of the chunk that ends there
def last_chunk(live, t):
    n = min(t, live)
    return live - n, n


@pytest.mark.parametrize("live", [1, 8, 9, 63, 64, 65, 192],
                         ids=["one_key", "a_page", "a_page_and_1",
                              "a_block_less_1", "a_block", "a_block_and_1",
                              "the_full_table"])
@pytest.mark.parametrize("h, hk", [(4, 4), (10, 2), (16, 2)],
                         ids=["G1", "G5", "G8"])
def test_chunk_against_the_dense_reference(small_blocks, h, hk, live):
    page, columns, t = 8, 24, 16
    assert ppa.block_pages(columns, page) * page == small_blocks
    start, n = last_chunk(live, t)
    case = chunk_case(h, hk, 8, page, columns, t, start, n, seed=live + h)
    got, want = both(case, h, hk, n)
    np.testing.assert_allclose(got, want, atol=ATOL["float32"], rtol=0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("h, hk, d", [(25, 25, 64), (20, 4, 128)],
                         ids=["gpt2", "falcon_h1"])
def test_chunk_at_the_modules_own_block(h, hk, d, dtype):
    """Pages of 128 and a table of 24: the block is the module's own
    (1,024 keys), the chunk's last key lies in the second."""
    page, columns, t = 128, 24, 16
    assert ppa.block_pages(columns, page) == ppa.BLOCK_KEYS // page < columns
    start, n = last_chunk(ppa.BLOCK_KEYS + 70, t)
    case = chunk_case(h, hk, d, page, columns, t, start, n, seed=3,
                      dtype=jnp.dtype(dtype))
    got, want = both(case, h, hk, n)
    np.testing.assert_allclose(got, want, atol=ATOL[dtype], rtol=0)


@pytest.mark.parametrize("t", [10, 48])
def test_paged_prefill_attention_matches_contiguous_reference(t):
    """The whole prompt in one chunk against the training path's
    `dense_attention` over the contiguous keys, within float32
    roundoff: the garbage tail of the last page weighs nothing."""
    from deepspeed_tpu.ops.transformer.flash_attention import \
        dense_attention
    h, d = 4, 16
    case = chunk_case(h, h, d, 16, 4, t, 0, t, seed=3)
    q, k_pool, v_pool, li, tables = case[:5]
    keys = [np.asarray(pool)[li, tables[0]].reshape(-1, pool.shape[-1])
            [:t, :h * d].reshape(1, t, h, d) for pool in (k_pool, v_pool)]
    ref = np.asarray(jax.jit(
        lambda q, k, v: dense_attention(q, k, v, causal=True))(
            q.reshape(1, t, h, d), *keys)).reshape(1, t, h * d)
    got, _ = both(case, h, h, t)
    np.testing.assert_allclose(ref, got, atol=2e-6, rtol=0)


@pytest.mark.parametrize("start", [0, 5, 40, 100, 173])
@pytest.mark.parametrize("block", [16, None], ids=["blocks_of_4_pages",
                                                   "one_block"])
def test_a_ring_whose_walk_wraps(monkeypatch, block, start):
    """A window of 24 over pages of 4 and chunks of 16: a ring of 11
    columns, which the walk enters at the page of the chunk's first
    visible key (column p % 11) and leaves by wrapping; a block's
    last columns name pages that lie ahead of the chunk or behind its
    window."""
    h, hk, d, page, window, t = 6, 2, 8, 4, 24, 16
    ring = ring_columns(window, page, t)
    if block:
        monkeypatch.setattr(ppa, "BLOCK_KEYS", block)
    n = 16 if start != 173 else 9          # a last chunk with pad rows
    case = chunk_case(h, hk, d, page, ring, t, start, n, seed=3 + start,
                      window=window)
    assert case[-1] == ring == 11
    got, want = both(case, h, hk, n)
    np.testing.assert_allclose(got, want, atol=ATOL["float32"], rtol=0)


@pytest.mark.parametrize("window", [0, 24], ids=["straight", "ring"])
@pytest.mark.parametrize("live", [1, 70, 150])
def test_garbage_where_no_visible_key_lies_changes_nothing(small_blocks,
                                                           live, window):
    """Equal bit for bit to the result over pools whose rows without a
    visible key (scratch page 0 among them) are zero."""
    h, hk, d, page, t = 10, 2, 8, 8, 16
    columns = ring_columns(window, page, t) if window else 24
    start, n = last_chunk(live, t)
    results = [both(chunk_case(h, hk, d, page, columns, t, start, n,
                               seed=live, window=window, garbage=garbage),
                    h, hk, n)[0] for garbage in (True, False)]
    assert np.array_equal(*results)


@pytest.mark.parametrize("live", [1, 64, 70, 121])
@pytest.mark.parametrize("h, hk", [(4, 4), (10, 2)], ids=["G1", "G5"])
def test_the_result_does_not_depend_on_the_tables_width(small_blocks, h,
                                                        hk, live):
    """The same live keys behind tables of 16, 24 and 40 columns, all
    wider than a block: the walk is the same blocks, and the result
    equal bit for bit (the dense form summed a longer row of exact
    zeros, in whatever blocking the backend picked for it)."""
    page, t = 8, 16
    start, n = last_chunk(live, t)
    results = []
    for columns in (16, 24, 40):
        case = list(chunk_case(h, hk, 8, page, 40, t, start, n, seed=live))
        case[4] = case[4][:, :columns]
        results.append(both(case, h, hk, n)[0])
    assert np.array_equal(results[0], results[1])
    assert np.array_equal(results[0], results[2])


@pytest.mark.parametrize("ringed", [False, True], ids=["straight", "ring"])
@pytest.mark.parametrize("start", [0, 5, 40])
def test_prefill_attention_with_a_first_visible_key(start, ringed):
    """A window of 24 at 6 heads over 2: through the straight table of
    whole histories (the keys below a query's first are there and are
    masked), and through the ring (they are released)."""
    h, hk, d, page, window, t = 6, 2, 8, 4, 24, 16
    columns = ring_columns(window, page, t) if ringed else 16
    case = chunk_case(h, hk, d, page, columns, t, start, t, seed=3 + start,
                      window=window, ringed=ringed)
    np.testing.assert_allclose(*both(case, h, hk, t), atol=1e-5, rtol=0)


@pytest.mark.parametrize("h, d", [(25, 64), (20, 128)],
                         ids=["gpt2", "falcon_h1"])
def test_prefill_attention_without_a_bound_is_as_it_was(h, d):
    """No `first`: the causal mask over the keys in order; a bound of 0
    through the straight table walks the same pages and masks nothing
    more, bit for bit."""
    case = chunk_case(h, h, d, 8, 6, 16, 20, 16, seed=11)
    *operands, _, _ = case
    plain = launch(ppa.paged_prefill_attention, *operands, h, h)
    bounded = launch(ppa.paged_prefill_attention, *operands, h, h,
                     first=np.zeros_like(case[5]))
    assert np.array_equal(np.asarray(plain), np.asarray(bounded))
    np.testing.assert_allclose(*both(case, h, h, 16), atol=1e-5, rtol=0)


@pytest.mark.parametrize("page, columns, chunk, window", [
    (128, 128, 512, 0),      # Falcon-H1's cell: blocks of 8 pages
    (128, 21, 512, 2048),    # Trinity's ring: 8 of its 21 columns a block
    (16, 64, 128, 0),        # GPT-2's cell: the whole row is one block
], ids=["falcon_h1", "trinity_ring", "gpt2"])
def test_the_walk_on_host_numbers(page, columns, chunk, window):
    """`walk` by hand: the pages from the first visible key's to the
    last key's, in blocks of `block_pages`."""
    bp = ppa.block_pages(columns, page)
    assert bp == min(columns, ppa.BLOCK_KEYS // page)
    keys = bp * page
    for start in (0, page - 1, chunk, keys - chunk, keys - chunk + 1,
                  3 * keys + 5):
        last = start + chunk - 1
        if not window and last >= columns * page:
            continue
        first = max(start - window + 1, 0) if window else 0
        pages = range(first // page, last // page + 1)
        assert len(pages) <= columns
        page0, blocks = ppa.walk(last, page, columns, first)
        assert page0 == pages[0] and blocks == -(-len(pages) // bp)
        assert blocks == 1 or columns > bp


def test_the_loop_gathers_a_block_and_repeats_no_key(small_blocks):
    """The entry's jaxpr at G = 5 over a table wider than a block: one
    `while` whose gathers take a block's pages out of the pools, and
    no array of the query head count over a block's or the table's
    keys (keys or values repeated)."""
    h, hk, d, page, columns, t = 10, 2, 8, 8, 24, 16
    *operands, _, _ = chunk_case(h, hk, d, page, columns, t, 100, 16, seed=0)
    jaxpr = jax.make_jaxpr(functools.partial(
        ppa.paged_prefill_attention, n_head=h, n_kv_head=hk))(*operands).jaxpr
    assert [e.primitive.name for e in jaxpr.eqns].count("while") == 1
    bp = small_blocks // page
    gathers = [e for e in equations(jaxpr) if e.primitive.name == "gather"
               and e.invars[0].aval.ndim == 4]
    assert len(gathers) == 2
    for eqn in gathers:
        assert eqn.outvars[0].aval.shape[:2] == (1, bp)
    for eqn in equations(jaxpr):
        for var in eqn.outvars:
            shape = var.aval.shape
            assert not (h in shape and (small_blocks in shape or
                                        columns * page in shape)), eqn


# ----------------------------------------------------------------------
# the three kinds' prefill programs, and the fence rows' counter
# ----------------------------------------------------------------------
# pages of 4 under blocks of 8 keys: a table of 32 columns is walked
# in up to sixteen blocks, Trinity's ring of 8 in four
SERVE = {"max_slots": 3, "prefill_chunk": 16, "sync_every": 2,
         "max_new_tokens": 8, "max_seq_len": 128,
         "kv_cache": {"num_pages": 60, "page_size": 4}}
BLOCK = 8


def tiny_engine(kind, monkeypatch):
    """(engine, its jaxprs by program) of a tiny model of `kind`."""
    from deepspeed_tpu.inference import InferenceEngine
    from paged_oracle import traced_programs
    monkeypatch.setattr(ppa, "BLOCK_KEYS", BLOCK)
    monkeypatch.setattr(latent_attention, "_BLOCK_KEYS", BLOCK)
    if kind == "paged":
        from deepspeed_tpu.models.gpt2 import (GPT2ForCausalLM,
                                               tiny_gpt2_config)
        cfg = tiny_gpt2_config()
        params = GPT2ForCausalLM(cfg).init(
            jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    elif kind == "paged+latent":
        import test_sarvam_mla
        _, cfg, params, _ = test_sarvam_mla.tiny(4)
    else:
        import test_falcon_h1
        import test_trinity
        cfg, params, _ = {"paged+state": test_falcon_h1,
                          "paged+window": test_trinity}[kind].tiny(
                              jnp.float32)
    with traced_programs() as jaxprs:
        engine = InferenceEngine(cfg, params, {"inference": SERVE})
    assert engine.serving.mc.cache_kind == kind
    return engine, jaxprs


KINDS = ["paged", "paged+state", "paged+window"]


@pytest.mark.parametrize("kind", KINDS)
def test_prefill_programs_gather_no_whole_row_and_repeat_no_key(
        kind, monkeypatch):
    """The prefill program of each kind, its table wider than a block:
    every gather out of a pool takes a block's pages (never the table
    row's `max_pages`), and no array carries the query head count over
    a block's or a table's keys where heads are grouped (keys or values
    repeated to [.., keys, n_head, head_dim])."""
    engine, jaxprs = tiny_engine(kind, monkeypatch)
    mc, page = engine.serving.mc, SERVE["kv_cache"]["page_size"]
    pools = {engine._state[k].shape for k in engine.serving.kind.keys
             if engine._state[k].ndim == 4 and
             engine._state[k].shape[2] == page}
    widths = {t.shape[1] for t in jax.tree_util.tree_leaves(
        engine.cache.tables)}
    assert max(widths) == 32 and min(widths) * page > BLOCK
    bp = BLOCK // page
    gathers = [e for e in equations(jaxprs["prefill_fn"])
               if e.primitive.name == "gather" and
               e.invars[0].aval.shape in pools]
    assert gathers
    for eqn in gathers:
        assert eqn.outvars[0].aval.shape[:2] == (1, bp), eqn
    h, hk = mc.n_head, getattr(mc, "n_kv_head", mc.n_head)
    keys = {BLOCK} | {w * page for w in widths}
    for eqn in equations(jaxprs["prefill_fn"]) if h != hk else ():
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert not (shape[-2:] == (h, mc.head_dim) and
                        keys & set(shape[:-2])), eqn


def keys_by_hand(start, n, page, columns, window=0):
    """Blocks of BLOCK keys from the page of the first visible key to
    the page of the launch's last key."""
    bp = min(columns, BLOCK // page)
    first = max(start - window + 1, 0) // page if window else 0
    pages = (start + n - 1) // page - first + 1
    return -(-pages // bp) * bp * page


@pytest.mark.parametrize("kind", ["paged", "paged+window", "paged+latent"])
def test_fence_rows_count_the_keys_prefill_walked(kind, monkeypatch):
    """`kv_prefill_keys_attended` / `kv_prefill_keys_tabled` (and the
    window pool's pair) over the fence rows of a short serving run
    against the schedule's own (start, n) pairs, by hand; the latent
    kind's one pool counts under the same names, in blocks of the
    latent forms' own size."""
    from deepspeed_tpu.inference import Request, ServingLoop
    engine, _ = tiny_engine(kind, monkeypatch)
    page = SERVE["kv_cache"]["page_size"]
    launches, rows = [], []
    real_chunk, real_event = engine.prefill_chunk, engine.monitor.event
    engine.prefill_chunk = lambda slot, tokens, start: (
        launches.append((start, len(tokens))),
        real_chunk(slot, tokens, start))[1]
    engine.monitor.event = lambda name, **kw: (
        rows.append(kw) if name == "decode_batch" else None,
        real_event(name, **kw))[1]
    rng = np.random.default_rng(5)
    ServingLoop(engine).serve([
        Request(rid=i, tokens=rng.integers(0, 90, n), max_new_tokens=6)
        for i, n in enumerate([70, 3, 41, 100])])
    # 69 = 4 x 16 + 5, 2, 40 = 2 x 16 + 8, 99 = 6 x 16 + 3
    assert len(launches) == 5 + 1 + 3 + 7
    assert sum(r["prefill_launches"] for r in rows) == len(launches)
    pools = {"kv_prefill_keys": (32, 0)}
    if kind == "paged+window":
        window = engine.cache.window
        assert window.ring == 8
        pools["kv_prefill_keys_window"] = (window.ring, window.window)
    for name, (columns, window) in pools.items():
        assert sum(r[name + "_attended"] for r in rows) == sum(
            keys_by_hand(start, n, page, columns, window)
            for start, n in launches)
        for row in rows:
            assert row[name + "_tabled"] == \
                row["prefill_launches"] * columns * page
        # the mechanism engaged: a launch walks fewer keys than its row
        assert 0 < sum(r[name + "_attended"] for r in rows) < \
            (1 if window else 0.6) * sum(r[name + "_tabled"] for r in rows)
    one = [r for r in rows if r["prefill_launches"] == 1]
    assert one and all(
        r["kv_prefill_keys_attended"] in {
            keys_by_hand(s, n, page, 32) for s, n in launches} for r in one)
