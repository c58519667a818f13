"""A prefill chunk's attention against a paged K/V cache, over the keys
the slot holds (XLA).

A chunk carries a few hundred query rows of ONE slot: a matrix-unit
problem, where the few rows a slot of decode, draft decode and verify
are a page walk (`paged_decode_attention`, the Mosaic kernel beside
this file, whose argument list this entry mirrors). It used to gather
the slot's WHOLE table row out of the pools, repeat keys and values to
the query head count and attend densely to the row, whatever the slot
held. This entry takes both whole pools as they ride in the layer
scan's carry,

    k_pool / v_pool : [n_layer, num_pages, page, lanes]

and walks the slot's pages from the page of its first visible key to
the page of `kv_limit`, a block of whole pages at a time: a loop whose
trip count follows the live length alone, each block gathered through
a slice of the table row, under an online softmax in float32 (running
max, sum and accumulator, as `latent_attention` keeps them). Pages past
the live length are never gathered. A table no wider than a block is
one block and no loop.

Grouped-query heads are not repeated: the G query heads that read a
key/value head lie side by side in the query rows, q [B, Hk, G*T, D]
against a block's [B, keys, Hk, D], one product batched over the
key/value heads (query head kv*G + j reads key/value head kv, as in
the decode kernel). With G = 1 it is the plain form.

A lower bound on the keys a query sees (`first`, a first visible key
a query row; a sliding window): the walk starts at the page of the
earliest query's first visible key, and through a table that is a RING
of `ring` columns it reads logical page p in column p % ring, as the
decode kernel does. A key's position is its logical page's.

Keys past a query's position, past `kv_limit` or below its `first`
are masked to -1e30 before the softmax and weigh an exact 0; rows of
the pools past `kv_limit` or below the earliest `first` may hold
anything and are zeroed before they are read as values.

The regions keep their names: a block's gather lies under
SCOPE_KV_GATHER, its products and softmax under SCOPE_ATTN.
"""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.transformer.flash_attention import NEG_INF
from deepspeed_tpu.utils.scopes import SCOPE_ATTN, SCOPE_KV_GATHER

f32 = jnp.float32
# keys a block of the walk, where the table is wider. On the chip a key
# costs nearly the same in blocks of 512, 1,024 and 2,048 (0.065 to
# 0.071 us a layer in Falcon-H1's geometry, 20 query heads over 4 of
# 128 and 512 query rows) while a block's float32 scores stay in the
# chip's near memory, so what a block costs is its overshoot past the
# last key, half a block on average, against a loop turn's 0.03 ms;
# Trinity's 32 heads x 512 rows x 2,048 keys (134 MB) do not stay
# there, and a block costs 2.7 times what two of 1,024 do
# (PERF.md section 6, PR 42)
BLOCK_KEYS = 1024


def block_pages(columns, page):
    """Pages a block of the walk through a table of `columns` columns:
    chosen from the shapes, the whole table where it is no wider than
    BLOCK_KEYS keys."""
    return max(1, min(int(columns), BLOCK_KEYS // int(page)))


def walk(kv_limit, page, columns, first=0):
    """(first page, blocks) of the walk for a chunk whose last key is
    `kv_limit` and whose earliest query sees no key below `first`: the
    program's own trip count, and on host numbers the reckoning of
    what a launch gathered (`walked_keys`)."""
    page0 = first // page
    pages = kv_limit // page - page0 + 1
    return page0, -(-pages // block_pages(columns, page))


def walked_keys(kv_limit, page, columns, first=0):
    """The keys such a chunk's walk gathers in a layer: whole blocks."""
    _, blocks = walk(kv_limit, page, columns, first)
    return blocks * block_pages(columns, page) * page


def paged_prefill_attention(q, k_pool, v_pool, li, tables, q_pos, kv_limit,
                            n_head, n_kv_head, first=None, ring=0):
    """q [B, T, n_head * D] against layer `li` of k_pool / v_pool
    [L, P, page, lanes] through tables [B, columns] (a chunk: B = 1).
    Row (b, t) sits at position q_pos[b, t] and sees keys at positions
    <= it, <= kv_limit[b] and, with `first` [B, T], >= first[b, t];
    `ring` > 0: the table is a ring of that many columns. Returns
    [B, T, n_head * D] in q's type."""
    b, t, _ = q.shape
    h, hk = n_head, n_kv_head
    g, d = h // hk, q.shape[-1] // h
    c = hk * d
    page, columns = k_pool.shape[2], tables.shape[1]
    bp = block_pages(columns, page)
    keys = bp * page
    sm_scale = 1.0 / np.sqrt(d)
    precision = jax.lax.Precision.HIGHEST if q.dtype == f32 else None
    # the earliest visible key of the launch: the rows of a slot ascend
    low = None if first is None else jnp.min(first)
    page0, n_blocks = walk(jnp.max(kv_limit), page, columns,
                           0 if low is None else low)
    # a block's columns are one slice of the row: a ring's first columns
    # again behind its last (a block is no wider than the ring), scratch
    # page 0 behind a straight table's
    tables = jnp.concatenate([tables, tables[:, :bp]], axis=1) if ring \
        else jnp.pad(tables, ((0, 0), (0, bp)))
    # the G query heads of a key/value head side by side in the rows
    qg = q.reshape(b, t, hk, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b, hk, g * t, d)
    q_pos = q_pos[:, None, None, :, None]                 # [B, 1, 1, T, 1]
    upper = jnp.minimum(q_pos, kv_limit[:, None, None, None, None])
    lower = None if first is None else first[:, None, None, :, None]

    def block(i, carry):
        m, l, acc = carry
        at = page0 + i * bp
        kpos = at * page + jnp.arange(keys)
        with jax.named_scope(SCOPE_KV_GATHER):
            cols = jax.lax.dynamic_slice_in_dim(
                tables, at % ring if ring else at, bp, axis=1)
            held = kpos[None, :] <= kv_limit[:, None]          # [B, K]
            if low is not None:
                held = held & (kpos[None, :] >= low)
            kb = k_pool[li, cols][..., :c].reshape(b, keys, hk, d)
            vb = v_pool[li, cols][..., :c].reshape(b, keys, hk, d)
            vb = jnp.where(held[:, :, None, None], vb,
                           jnp.zeros((), vb.dtype))
        with jax.named_scope(SCOPE_ATTN):
            s = jnp.einsum("bhqd,bkhd->bhqk", qg, kb,
                           preferred_element_type=f32,
                           precision=precision) * sm_scale
            s = s.reshape(b, hk, g, t, keys)
            seen = kpos <= upper                         # [B, 1, 1, T, K]
            if lower is not None:
                seen = seen & (kpos >= lower)
            s = jnp.where(seen, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            l = alpha * l + p.sum(-1, keepdims=True)
            pv = jnp.einsum(
                "bhqk,bkhd->bhqd",
                p.astype(vb.dtype).reshape(b, hk, g * t, keys), vb,
                preferred_element_type=f32, precision=precision)
            acc = alpha * acc + pv.reshape(b, hk, g, t, d)
        return m_new, l, acc

    rows = (b, hk, g, t, 1)
    carry = (jnp.full(rows, NEG_INF, f32), jnp.zeros(rows, f32),
             jnp.zeros((b, hk, g, t, d), f32))
    if columns <= bp:
        _, l, acc = block(0, carry)
    else:
        _, l, acc = jax.lax.fori_loop(0, n_blocks, block, carry)
    with jax.named_scope(SCOPE_ATTN):
        out = acc / jnp.where(l > 0, l, 1.0)
        return out.astype(q.dtype).transpose(0, 3, 1, 2, 4) \
            .reshape(b, t, h * d)
