"""A lower bound on the keys a query sees (ISSUE 35): the decode
kernel with a first visible key a query row and a ring of pages,
against a dense masked oracle in float32 numpy; and with no bound
given, against what it gave before at GPT-2's and Falcon-H1's shapes (a
bound of 0 through the straight table is the kernel without one, bit
for bit). A prefill chunk's bound and ring: `test_paged_prefill_
attention.py`, where ISSUE 42 moved this file's two chunk tests.

The ring. A window layer's table has `ring` columns and logical page p
of a slot lies in column p % ring; pages behind the window have been
given back, so their columns hold pages that lie AHEAD (or scratch page
0), and every physical page that no visible key lies in is filled with
finite garbage of large magnitude: it must contribute exactly nothing.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kv_cache import ring_columns
from deepspeed_tpu.ops.transformer.paged_decode_attention import (
    padded_lanes, paged_decode_attention)
from test_paged_decode_attention import ATOL, GARBAGE, make_case, reference


def ring_case(n_head, n_kv_head, head_dim, page, window, tq, dtype, seed):
    """Slots at lengths round the window's edges, their K/V in a ring
    of pages: (q, k_pool, v_pool, li, ring tables, q_pos, lens, first,
    keys [B] of (positions, k rows, v rows) that are visible to some
    row)."""
    rng = np.random.default_rng(seed)
    c = n_kv_head * head_dim
    lanes = padded_lanes(c)
    ring = ring_columns(window, page, tq)
    lengths = [0, 1, window - 1, window, window + 1, window + page,
               2 * window + 3, 5 * window + page // 2, page + 1,
               3 * ring * page + 7]
    lens = np.asarray([max(n, tq) if n else 0 for n in lengths], np.int32)
    b = len(lens)
    q_pos = np.maximum(lens[:, None] - tq + np.arange(tq)[None], 0) \
        .astype(np.int32)
    first = np.maximum(q_pos - window + 1, 0).astype(np.int32)
    n_pages = b * ring + 1
    phys = rng.permutation(np.arange(1, n_pages)).reshape(b, ring)
    tables = np.zeros((b, ring), np.int32)
    li, n_layer = 1, 2
    shape = (n_layer, n_pages, page, lanes)
    sign = rng.choice([-1.0, 1.0], size=shape)
    pools = [(sign * GARBAGE * (1 + rng.random(shape))).astype(np.float32)
             for _ in range(2)]
    keys = []
    for s in range(b):
        if not lens[s]:
            keys.append(None)
            continue
        lo, hi = int(first[s, 0]), int(lens[s])
        rows = [rng.normal(size=(hi - lo, c)) for _ in range(2)]
        for p in range(lo // page, (hi - 1) // page + 1):
            tables[s, p % ring] = phys[s, p % ring]
        for pool, r in zip(pools, rows):
            for pos in range(lo, hi):
                pool[li, tables[s, (pos // page) % ring], pos % page, :c] = \
                    r[pos - lo]
        keys.append((np.arange(lo, hi), *rows))
    q = jnp.asarray(rng.normal(size=(b, tq, n_head * head_dim)), dtype)
    k_pool, v_pool = (jnp.asarray(p, dtype) for p in pools)
    return q, k_pool, v_pool, li, tables, q_pos, lens, first, ring, keys


def dense_oracle(q, keys, q_pos, first, n_head, n_kv_head, dtype):
    """float32: every row against the slot's keys in [first, q_pos],
    one query head at a time."""
    q = np.asarray(q, np.float32)
    b, tq, c = q.shape
    d, group = c // n_head, n_head // n_kv_head
    out = np.zeros((b, tq, c), np.float32)
    for s in range(b):
        if keys[s] is None:
            continue
        at, k, v = keys[s]
        k, v = (np.asarray(jnp.asarray(x, dtype), np.float32)
                for x in (k, v))
        for r in range(tq):
            seen = (at >= first[s, r]) & (at <= q_pos[s, r])
            for h in range(n_head):
                cols = slice(h * d, (h + 1) * d)
                held = slice(h // group * d, (h // group + 1) * d)
                scores = k[seen][:, held] @ q[s, r, cols] / np.sqrt(d)
                p = np.exp(scores - scores.max())
                out[s, r, cols] = (p / p.sum()) @ v[seen][:, held]
    return out


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv_head", "ring"))
def launch(q, k_pool, v_pool, li, tables, q_pos, lens, n_head, n_kv_head,
           first=None, ring=None):
    return paged_decode_attention(q, k_pool, v_pool, li, tables, q_pos,
                                  lens, n_head, n_kv_head, first=first,
                                  ring=ring)


@pytest.mark.parametrize("tq", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_head, n_kv_head, head_dim, page, window", [
    (32, 4, 128, 16, 40),    # Trinity's row of 512 lanes, G 8; the
    #                          window no multiple of the page
    (6, 2, 16, 4, 24),       # 32 lanes padded to 128, a ring of 8
    (4, 4, 32, 8, 8),        # a window of ONE page
], ids=["32over4x128", "6over2x16", "4x32"])
def test_kernel_over_a_ring_against_the_dense_masked_oracle(
        n_head, n_kv_head, head_dim, page, window, dtype, tq):
    (q, k_pool, v_pool, li, tables, q_pos, lens, first, ring,
     keys) = ring_case(n_head, n_kv_head, head_dim, page, window, tq,
                       jnp.dtype(dtype), seed=7 + tq)
    got = launch(q, k_pool, v_pool, li, tables, q_pos, lens, n_head,
                 n_kv_head, first=first, ring=ring)
    assert got.dtype == q.dtype and got.shape == q.shape
    got32 = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got32).all()
    want = dense_oracle(q, keys, q_pos, first, n_head, n_kv_head, dtype)
    np.testing.assert_allclose(got32, want, atol=ATOL[dtype], rtol=0)
    assert not got32[lens == 0].any()
    # a row of several equals the launch of that row alone
    for r in range(tq if tq > 1 else 0):
        alone = launch(q[:, r:r + 1], k_pool, v_pool, li, tables,
                       q_pos[:, r:r + 1], np.minimum(lens, q_pos[:, r] + 1),
                       n_head, n_kv_head, first=first[:, r:r + 1], ring=ring)
        # the walk starts at row 0's page: a later row alone starts at
        # its own, and sums the same visible keys in another grouping
        np.testing.assert_allclose(
            np.asarray(alone[:, 0].astype(jnp.float32)), got32[:, r],
            atol=ATOL[dtype], rtol=0)


def test_a_ring_needs_the_first_visible_key():
    q = jnp.zeros((1, 1, 32))
    pool = jnp.zeros((1, 3, 8, 128))
    with pytest.raises(ValueError, match="first visible key"):
        paged_decode_attention(q, pool, pool, 0, np.zeros((1, 2), np.int32),
                               np.zeros((1, 1), np.int32),
                               np.ones((1,), np.int32), 4, ring=2)


@pytest.mark.parametrize("tq", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_head, n_kv_head, head_dim, page, max_pages", [
    (25, None, 64, 16, 9),   # GPT-2 1.5B's row
    (20, 4, 128, 16, 9),     # Falcon-H1's row
], ids=["gpt2", "falcon_h1"])
def test_no_bound_is_the_kernel_as_it_was(n_head, n_kv_head, head_dim, page,
                                          max_pages, dtype, tq):
    """With no `first` the kernel takes the operands it took and gives
    what `test_paged_decode_attention.py` holds it to; a bound of 0
    through the straight table walks the same pages from page 0 and
    masks nothing more: equal bit for bit."""
    q, k_pool, v_pool, zeroed, li, tables, q_pos, lens = make_case(
        n_head, head_dim, page, max_pages, tq, jnp.dtype(dtype), seed=tq,
        n_kv_head=n_kv_head)
    plain = launch(q, k_pool, v_pool, li, tables, q_pos, lens, n_head,
                   n_kv_head)
    want = reference(q, *zeroed, li, tables, q_pos, lens, n_head, n_kv_head)
    np.testing.assert_allclose(np.asarray(plain.astype(jnp.float32)), want,
                               atol=ATOL[dtype], rtol=0)
    bounded = launch(q, k_pool, v_pool, li, tables, q_pos, lens, n_head,
                     n_kv_head, first=np.zeros_like(q_pos))
    assert np.array_equal(np.asarray(plain), np.asarray(bounded))
    closed = jax.make_jaxpr(functools.partial(
        paged_decode_attention, n_head=n_head, n_kv_head=n_kv_head))(
            q, k_pool, v_pool, li, tables, q_pos, lens)
    (call,) = [e for e in closed.jaxpr.eqns if e.primitive.name ==
               "pallas_call"]
    # li, tables, lens, q_pos; q, the two pools
    assert len(call.invars) == 7
