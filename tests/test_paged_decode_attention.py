"""The decode kernel that reads K/V pages where they lie
(`ops/transformer/paged_decode_attention.py`, ISSUE 27), in the Pallas
interpreter on the CPU, against a float32 reference that gathers each
slot's keys in numpy and attends to all of them head by head.

One batch of slots a case carries every length that matters: 0 (a slot
that is not live), 1, one short of a page, a page, one past it, one
past a compute block of 128 keys where the window reaches that far,
the full window. The page tables are a scrambled permutation; every row
of the pools that no live slot has written (scratch page 0, pages
nobody holds, the rest of a slot's last page, its pages past its
length) is filled with finite garbage of large magnitude, pad lanes
included, and must contribute exactly nothing: the result is equal bit
for bit to the one over pools whose unwritten rows are zero.

What the tolerances are. float32: the kernel and the reference sum the
same products in different orders; 1e-5 is 10x the largest seen
(2e-7 to 1.1e-6). bfloat16: operands are the pools' dtype, every sum
is float32, the probabilities are rounded to bfloat16 before the
product with V and the output is rounded to bfloat16; 2e-2 on values
of size about 1 is 2.5x the largest seen (4e-3 to 8e-3), and a missed
or doubled key is 0.1 and more.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.paged_decode_attention import (
    padded_lanes, paged_decode_attention)

ATOL = {"float32": 1e-5, "bfloat16": 2e-2}
GARBAGE = 3e4


def reference(q, k_pool, v_pool, li, tables, q_pos, lens, n_head,
              n_kv_head=None):
    """float32, all keys of a slot gathered, one query head at a time
    against the key/value head it reads."""
    q, k_pool, v_pool = (np.asarray(x, np.float32)
                         for x in (q, k_pool, v_pool))
    b, tq, c = q.shape
    d = c // n_head
    group = n_head // (n_kv_head or n_head)
    out = np.zeros((b, tq, c), np.float32)
    for s in range(b):
        if lens[s] == 0:
            continue
        k = k_pool[li][tables[s]].reshape(-1, k_pool.shape[-1])
        v = v_pool[li][tables[s]].reshape(-1, k_pool.shape[-1])
        for r in range(tq):
            n = min(lens[s], q_pos[s, r] + 1)
            for h in range(n_head):
                cols = slice(h * d, (h + 1) * d)
                held = slice(h // group * d, (h // group + 1) * d)
                scores = k[:n, held] @ q[s, r, cols] / np.sqrt(d)
                p = np.exp(scores - scores.max())
                out[s, r, cols] = (p / p.sum()) @ v[:n, held]
    return out


def make_case(n_head, head_dim, page, max_pages, tq, dtype, seed,
              n_kv_head=None):
    """(q, k_pool, v_pool, zeroed pools, li, tables, q_pos, lens). The
    pools hold `n_kv_head` heads a token (default: `n_head`)."""
    rng = np.random.default_rng(seed)
    c = (n_kv_head or n_head) * head_dim
    lanes = padded_lanes(c)
    window = page * max_pages
    lengths = [0, 1, page - 1, page, page + 1, window, window - 3, 0,
               min(129, window), 2 * page + 5]
    lens = np.asarray([max(n, tq) if n else 0 for n in lengths], np.int32)
    b = len(lens)
    n_pages = b * max_pages + 3
    # a scrambled table: no slot's pages are neighbours or in order
    tables = rng.permutation(np.arange(1, n_pages))[:b * max_pages] \
        .reshape(b, max_pages).astype(np.int32)
    # the rows of a launch sit at the slot's last tq positions, except
    # in the last slot, whose later rows lie past its length (a verify
    # round with fewer valid drafts than rows)
    q_pos = np.maximum(lens[:, None] - tq + np.arange(tq)[None], 0)
    q_pos[-1] = lens[-1] - 2 + np.arange(tq)
    q_pos = q_pos.astype(np.int32)
    li, n_layer = 1, 3
    shape = (n_layer, n_pages, page, lanes)
    sign = rng.choice([-1.0, 1.0], size=shape)
    garbage = (sign * GARBAGE * (1 + rng.random(shape))).astype(np.float32)
    pools = []
    for _ in range(2):
        held = np.zeros(shape, bool)
        real = np.zeros(shape, np.float32)
        for s in range(b):
            rows = rng.normal(size=(lens[s], c))
            for pos in range(lens[s]):
                where = (li, tables[s, pos // page], pos % page)
                real[where][:c] = rows[pos]
                held[where] = True
        pools.append((jnp.asarray(np.where(held, real, garbage), dtype),
                      jnp.asarray(real, dtype)))
    (k_pool, k_zeroed), (v_pool, v_zeroed) = pools
    q = jnp.asarray(rng.normal(size=(b, tq, n_head * head_dim)), dtype)
    return q, k_pool, v_pool, (k_zeroed, v_zeroed), li, tables, q_pos, lens


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv_head"))
def launch(q, k_pool, v_pool, li, tables, q_pos, lens, n_head,
           n_kv_head=None):
    return paged_decode_attention(q, k_pool, v_pool, li, tables, q_pos,
                                  lens, n_head, n_kv_head)


@pytest.mark.parametrize("tq", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_head, n_kv_head, head_dim, page, max_pages", [
    (25, None, 64, 16, 9),   # GPT-2 1.5B's row: 1,600 lanes padded to 1,664
    (4, None, 32, 8, 5),     # a row of exactly one lane tile
    (6, None, 16, 4, 40),    # 96 lanes padded to 128; 32 pages a block
    # grouped-query heads (ISSUE 31): the pools hold n_kv_head heads
    (20, 4, 128, 16, 9),     # Falcon-H1's row: 4 x 128 = four lane tiles
    (6, 2, 16, 4, 40),       # G = 3 over 32 lanes padded to 128
    (8, 8, 16, 8, 5),        # G = 1 said aloud: the default, bit for bit
], ids=["25x64", "4x32", "6x16", "20over4x128", "6over2x16", "8over8x16"])
def test_kernel_against_float32_all_keys_reference(
        n_head, n_kv_head, head_dim, page, max_pages, dtype, tq):
    q, k_pool, v_pool, zeroed, li, tables, q_pos, lens = make_case(
        n_head, head_dim, page, max_pages, tq, jnp.dtype(dtype), seed=tq,
        n_kv_head=n_kv_head)
    got = launch(q, k_pool, v_pool, li, tables, q_pos, lens, n_head,
                 n_kv_head)
    if n_kv_head == n_head:
        default = launch(q, k_pool, v_pool, li, tables, q_pos, lens, n_head)
        assert np.array_equal(np.asarray(default), np.asarray(got))
    assert got.dtype == q.dtype and got.shape == q.shape
    got32 = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got32).all()

    want = reference(q, *zeroed, li, tables, q_pos, lens, n_head, n_kv_head)
    np.testing.assert_allclose(got32, want, atol=ATOL[dtype], rtol=0)
    # slots that are not live return zeros and read nothing
    assert not got32[lens == 0].any()
    # what no live slot has written contributes exactly nothing
    clean = launch(q, *zeroed, li, tables, q_pos, lens, n_head, n_kv_head)
    assert np.array_equal(np.asarray(clean), np.asarray(got))

    # a row of a launch of several equals, bit for bit, the launch of
    # one row at that position (whose slot is as long as the row sees)
    for r in range(tq if tq > 1 else 0):
        alone = launch(q[:, r:r + 1], k_pool, v_pool, li, tables,
                       q_pos[:, r:r + 1],
                       np.minimum(lens, q_pos[:, r] + 1), n_head, n_kv_head)
        assert np.array_equal(np.asarray(alone[:, 0]),
                              np.asarray(got[:, r])), r
