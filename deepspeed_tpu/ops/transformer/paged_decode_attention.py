"""Attention of a few query rows per slot against a paged K/V cache,
read where the pages lie (Pallas TPU kernel).

The serving programs that carry one to a handful of query rows per
slot (decode, draft decode, speculative verify) used to gather every
slot's whole page window out of the pools ([B, max_pages * page, H*D]),
re-lay it to [B, Tk, H, D] and run a dense masked attention over it:
16 x 1,024 keys in every layer, whatever was live. This kernel takes
both WHOLE pools as they ride in the layer scan's carry,

    k_pool / v_pool : [n_layer, num_pages, page, lanes]

(`PagedKVCache.pool_shape`: one token's K or V of one layer on the
lanes, `lanes` = H*D rounded up to a whole number of 128-lane tiles),
leaves them in HBM, and for each live slot walks that slot's page
table up to its length: pages are copied HBM -> VMEM one DMA each,
`_BLOCK_KEYS` keys to a compute block, double-buffered. A slot of
length 0 does nothing and returns zeros; pages past a slot's length
are never read.

Heads are never split out of the lanes. With the 0/1 indicator
E[h, l] = (l // D == h), per query row:

    Qbd    = E * q                       [Hp, lanes]   block-diagonal q
    scores = Qbd . Kblk^T * sm_scale     [Hp, keys]    fp32 accumulation
    (online softmax over key blocks, fp32; keys past the row's position
     or the slot's length masked to -1e30, their V rows zeroed)
    acc    = alpha * acc + P . Vblk      [Hp, lanes]   P in the pools' dtype
    out    = sum_h E * acc / l           [lanes]

The matrix unit computes every head against every lane and E keeps the
diagonal blocks: H times the useful products, on a unit that is
otherwise idle while the pages stream in. The operands are the pools'
dtype, every product sum accumulates in fp32, nothing is approximated.

Grouped-query heads: the pools hold Hk = H / G key/value heads (a row
of Hk*D lanes) and G query heads read each. The query rows come one
group member to a row, q [B, Tq*G, Hk*D] (row r*G + j holds, on
key/value head kv's lanes, query head kv*G + j), the indicator's row
j*Hk + kv keeps the lanes of kv from row j, E[h, l] = (l // D ==
h % Hk), and the result leaves the same way: Hk times the useful
products where H heads of their own cost H times. With G = 1 every
expression below is the one it was.

Every query row runs the same sequence of operations on the same page
blocks whatever Tq is (a static loop over the rows), so a row of a
Tq = k + 1 verify launch equals the Tq = 1 decode launch at that
position bit for bit: later keys are masked to an exact zero weight.

A lower bound on the keys a query sees (`first`, a first visible key
a query row): a layer that attends over a sliding window keeps only
the window's pages, in a table that is a RING of `ring` columns
(logical page p of a slot lies in column p % ring), and the walk
starts at the page of the slot's first visible key (row 0's: the rows
of a slot ascend) instead of at page 0. Keys below a row's `first`
are masked as keys past its position are. With no `first` the kernel
is, instruction for instruction, the one without this paragraph.

On a backend without Mosaic the same kernel runs in the Pallas
interpreter.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import NEG_INF, _on_tpu

LANE = 128
# keys per compute block: one lane tile of scores
_BLOCK_KEYS = 128
_VMEM_LIMIT = 32 * 1024 * 1024


def padded_lanes(width):
    """`width` (H*D) rounded up to a whole number of lane tiles: the
    row of a page pool."""
    return -(-int(width) // LANE) * LANE


def _kernel(li_ref, tables_ref, lens_ref, qpos_ref, *refs, n_head,
            n_kv_head, head_dim, sm_scale, precision, windowed, ring):
    # with a lower bound: one more scalar operand, the first visible
    # key of every query row
    first_ref = refs[0] if windowed else None
    (q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_ref, l_ref,
     acc_ref) = refs[1:] if windowed else refs
    s = pl.program_id(0)
    group = n_head // n_kv_head
    tq, lanes = q_ref.shape[1] // group, q_ref.shape[2]
    hp = acc_ref.shape[1]
    _, npb, page, _ = kbuf.shape
    bk = npb * page
    length = lens_ref[s]
    n_pages = (length + page - 1) // page
    # the walk's first page and key: 0 without a lower bound, and then
    # nothing is added anywhere (the kernel as it was, op for op)
    page0 = key0 = None
    if windowed:
        page0 = first_ref[s, 0] // page
        key0 = page0 * page
        n_pages = n_pages - page0
    after = lambda start, x: x if start is None else start + x
    n_blocks = (n_pages + npb - 1) // npb
    li = li_ref[0]

    def copies(blk, slot, i):
        column = after(page0, blk * npb + i)
        phys = tables_ref[s, column % ring if ring else column]
        return (pltpu.make_async_copy(k_hbm.at[li, phys], kbuf.at[slot, i],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[li, phys], vbuf.at[slot, i],
                                      sem.at[1, slot]))

    def for_pages_of(blk, slot, what):
        def one(i, carry):
            for copy in copies(blk, slot, i):
                what(copy)
            return carry
        jax.lax.fori_loop(0, jnp.minimum(n_pages - blk * npb, npb), one, 0)

    @pl.when(length == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(length > 0)
    def _():
        for_pages_of(0, 0, lambda copy: copy.start())
        head = jax.lax.broadcasted_iota(jnp.int32, (hp, lanes), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (hp, lanes), 1)
        # row `head` of the indicator reads key/value head `kv`
        kv = head if group == 1 else head % n_kv_head
        mine = (lane >= kv * head_dim) & (lane < (kv + 1) * head_dim) \
            & (head < n_head)
        # the rows of group member j (all of them where G = 1)
        member = [mine if group == 1 else mine & (head // n_kv_head == j)
                  for j in range(group)]

        def block_diagonal(r):
            rows = [jnp.where(member[j],
                              q_ref[0, r * group + j:r * group + j + 1, :],
                              0.0) for j in range(group)]
            return sum(rows[1:], rows[0]).astype(kbuf.dtype)

        qbd = [block_diagonal(r) for r in range(tq)]
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def block(blk, carry):
            slot = blk % 2

            @pl.when(blk + 1 < n_blocks)
            def _():
                for_pages_of(blk + 1, 1 - slot, lambda copy: copy.start())

            for_pages_of(blk, slot, lambda copy: copy.wait())
            k = kbuf[slot].reshape(bk, lanes)
            v = vbuf[slot].reshape(bk, lanes)
            # rows past the slot's length (the tail of its last page,
            # pages of this block that were not copied) hold anything
            row = after(key0, blk * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0))
            v = jnp.where(row < length, v, jnp.zeros((), v.dtype))
            kpos = after(key0, blk * bk + jax.lax.broadcasted_iota(
                jnp.int32, (1, bk), 1))
            for r in range(tq):
                scores = jax.lax.dot_general(
                    qbd[r], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=precision) * sm_scale
                seen = (kpos <= qpos_ref[s, r]) & (kpos < length)
                if windowed:
                    seen = seen & (kpos >= first_ref[s, r])
                scores = jnp.where(seen, scores, NEG_INF)
                m_prev = m_ref[r]
                m_new = jnp.maximum(
                    m_prev, jnp.max(scores, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(scores - m_new[:, :1])
                l_ref[r] = alpha * l_ref[r] + \
                    jnp.sum(p, axis=1, keepdims=True)
                m_ref[r] = m_new
                acc_ref[r] = alpha[:, :1] * acc_ref[r] + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=precision)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)
        for r in range(tq):
            heads = jnp.where(mine, acc_ref[r] / l_ref[r][:, :1], 0.0)
            for j in range(group):
                o_ref[0, r * group + j:r * group + j + 1, :] = jnp.sum(
                    heads if group == 1 else
                    jnp.where(member[j], heads, 0.0), axis=0, keepdims=True)


def paged_decode_attention(q, k_pool, v_pool, li, tables, q_pos, lens,
                           n_head, n_kv_head=None, first=None, ring=None):
    """Causal attention of q [B, Tq, H*D] (Tq a few rows) against layer
    `li` of the page pools [L, P, page, lanes], through the page tables
    [B, max_pages]. The pools hold `n_kv_head` heads a token (default:
    `n_head`); query head h reads key/value head h // (n_head //
    n_kv_head). Row r of slot b sits at absolute position
    q_pos[b, r] and sees keys at positions <= it and < lens[b], the
    slot's live length (0: the slot is not live; it returns zeros and
    reads nothing). Returns [B, Tq, H*D] in q's dtype. Rows of the
    pools at or past a slot's length may hold anything finite or not:
    they contribute exactly nothing.

    `first` [B, Tq]: row r of slot b sees no key below first[b, r]
    (a sliding window: q_pos - window + 1, floored at 0), and the page
    walk starts at the page of first[b, 0]. `ring`: the tables have
    that many columns and logical page p of a slot lies in column
    p % ring (a window layer keeps the window's pages only); without
    it column p."""
    if ring is not None and first is None:
        raise ValueError("a ring of pages needs the first visible key")
    b, tq, c = q.shape
    n_kv_head = n_head if n_kv_head is None else n_kv_head
    group = n_head // n_kv_head
    head_dim = c // n_head
    if group > 1:
        # one group member to a row, on its key/value head's lanes
        q = q.reshape(b, tq, n_kv_head, group, head_dim).transpose(
            0, 1, 3, 2, 4).reshape(b, tq * group, n_kv_head * head_dim)
        c = n_kv_head * head_dim
    _, _, page, lanes = k_pool.shape
    if lanes != padded_lanes(c) or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"pools {k_pool.shape} / {v_pool.shape} do not hold rows of "
            f"{c} lanes padded to {padded_lanes(c)}")
    interpret = not _on_tpu()
    dtype = k_pool.dtype
    sublanes = 8 * 4 // dtype.itemsize
    if not interpret and page % sublanes:
        raise ValueError(
            f"a page of {page} tokens is not a whole number of the chip's "
            f"{sublanes}-row tiles of {dtype}: a page cannot be copied "
            "alone (inference.kv_cache.page_size)")
    hp = -(-n_head // 16) * 16
    npb = max(1, _BLOCK_KEYS // page)
    kernel = functools.partial(
        _kernel, n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
        sm_scale=1.0 / np.sqrt(head_dim),
        precision=jax.lax.Precision.HIGHEST if dtype == jnp.float32
        else None, windowed=first is not None, ring=ring)
    row = lambda s, *_: (s, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if first is None else 5,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, tq * group, lanes), row),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tq * group, lanes), row),
        scratch_shapes=[
            pltpu.VMEM((2, npb, page, lanes), dtype),
            pltpu.VMEM((2, npb, page, lanes), dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((tq, hp, LANE), jnp.float32),
            pltpu.VMEM((tq, hp, LANE), jnp.float32),
            pltpu.VMEM((tq, hp, lanes), jnp.float32),
        ])
    q32 = jnp.pad(q, ((0, 0), (0, 0), (0, lanes - c))).astype(jnp.float32)
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tq * group, lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(li, (1,)).astype(jnp.int32), tables.astype(jnp.int32),
      lens.astype(jnp.int32), q_pos.astype(jnp.int32),
      *(() if first is None else (first.astype(jnp.int32),)),
      q32, k_pool, v_pool)
    out = out[..., :c].astype(q.dtype)
    if group > 1:
        out = out.reshape(b, tq, group, n_kv_head, head_dim).transpose(
            0, 1, 3, 2, 4).reshape(b, tq, n_head * head_dim)
    return out
