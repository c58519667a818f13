"""Attention over a paged cache of LATENT rows (multi-head latent
attention in its absorbed form): a token's cache row in a layer is ONE
vector shared by every head,

    row = [c~ ; k_rope]      kv_lora_rank + qk_rope_head_dim values

(`latent_kind.PagedLatentKind`'s pool [n_layer, num_pages, page,
lanes], the row on the lanes, zeros from its width up to the lane
tile), and it is
the key AND the value: with W_kvb^K absorbed into the query, head i
scores

    s_i(t, s) = q^_i . row_s        q^_i = [W_kvb,i^K q_nope_i ; q_rope_i]

and reads back the weighted sum of the rows' first `rank` values (the
c~ part), which the caller takes through W_kvb,i^V. The query comes
scaled; nothing here knows the model's softmax scale.

Three forms of the same numbers:

  * `latent_attention`: XLA. The slots' pages are gathered through
    their tables a block of `BLOCK_PAGES` pages at a time, as far as
    the longest slot reaches and no further (a loop of a dynamic trip
    count), under an online softmax in float32. It is both kernels'
    oracle and the path a backend without Mosaic takes, for a chunk
    and for decode; on a TPU nothing calls it (a chunk's float32
    scores, 64 heads x 512 rows x 512 keys, went out to HBM and back
    every block).
  * `latent_prefill_attention`: a Pallas TPU kernel for a prefill
    chunk (one slot, a few hundred query rows of 64 heads: a
    matrix-unit problem, a single-head flash attention whose query
    rows are tokens x heads). Per layer one call: a tile of
    `_TILE_ROWS` query rows a grid step, the pool left in HBM and
    walked through the slot's table row as the decode kernel walks it,
    as far as the tile's own last position; scores, statistics and
    accumulator never leave VMEM. By the shapes 75.5 MFLOP a key (the
    contraction runs over the pool's 640 lanes) against 1,280 B a
    query tile: bound by the matrix unit.
  * `latent_decode_attention`: a Pallas TPU kernel for ONE query row a
    slot (decode). Per layer one call: tables, lengths and the layer's
    index scalar-prefetched, the pool left in HBM, one page a DMA,
    `_BLOCK_KEYS` keys to a compute block, double-buffered; the block
    [keys, lanes] is the keys and, its first `rank` lanes, the
    values; a slot's H query rows make [H, lanes] x [lanes, keys] and
    [H, keys] x [keys, rank] on the matrix unit. A slot of length 0 reads nothing and returns zeros.
    By the shapes (64 heads, 576 + 512): 139 kFLOP against 1,152 B a
    cached token, memory bound.

Rows of the pool at or past a slot's length may hold anything: they
are zeroed before they are read as values and masked as keys.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import NEG_INF, _on_tpu
from deepspeed_tpu.ops.transformer.paged_decode_attention import LANE
from deepspeed_tpu.utils.scopes import SCOPE_ATTN, SCOPE_KV_GATHER

f32 = jnp.float32
_VMEM_LIMIT = 32 * 1024 * 1024
# pages a block of `latent_attention`: 512 keys at pages of 128
BLOCK_PAGES = 4
# keys a compute block of the decode kernel: four pages of 128 in
# flight together. On the chip one page a block reads 27% of the
# memory bound, two 37%, four 45% (PERF.md section 6, PR 39): a block's
# fixed costs (the waits, the loop) are shared by its pages
_BLOCK_KEYS = 512
# query rows a tile of the prefill kernel: 16 tokens of 64 heads. On the
# chip a key costs 0.46 us a layer at 1,024 rows and 0.48 at 512 (the
# keys are read twice as often); 2,048 rows read 5% less and take twice
# the VMEM limit and code (PERF.md section 6, PR 43)
_TILE_ROWS = 1024


def usable():
    """Whether decode and a prefill chunk attend through their Mosaic
    kernels (a TPU) or through the XLA form."""
    return _on_tpu()


def block_pages(columns, page):
    """Pages a compute block of the kernels (and, in the cell's
    geometry, of `latent_attention`): `_BLOCK_KEYS` keys, the whole
    table row where it is no wider."""
    return max(1, min(int(columns), _BLOCK_KEYS // int(page)))


def walked_keys(kv_limit, page, columns):
    """The keys a prefill launch whose last key is `kv_limit` attends
    to in a layer, as the fence rows count them: the whole blocks from
    the slot's first key to that one. Host arithmetic."""
    keys = block_pages(columns, page) * page
    return -(-(kv_limit + 1) // keys) * keys


def latent_attention(q, pool, li, tables, q_pos, lens, rank):
    """q [B, T, H, W] (scaled; W the row's width) against layer `li`
    of pool [L, P, page, lanes] through tables [B, max_pages]. Row
    (b, t) sits at position q_pos[b, t] and sees keys at positions <=
    it and < lens[b]. Returns [B, T, H, rank] in q's type; zeros for a
    slot of length 0."""
    b, t, h, w = q.shape
    page = pool.shape[2]
    bp = min(BLOCK_PAGES, tables.shape[1])
    keys = bp * page
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % bp)))
    n_blocks = -(-jnp.max(lens) // keys)
    q2 = q.reshape(b, t * h, w)
    precision = jax.lax.Precision.HIGHEST if q.dtype == f32 else None

    def block(i, carry):
        m, l, acc = carry
        kpos = i * keys + jnp.arange(keys)
        held = kpos[None, :] < lens[:, None]                  # [B, K]
        with jax.named_scope(SCOPE_KV_GATHER):
            cols = jax.lax.dynamic_slice_in_dim(tables, i * bp, bp, axis=1)
            rows = pool[li, cols][..., :w].reshape(b, keys, w)
            rows = jnp.where(held[..., None], rows, 0).astype(q.dtype)
        with jax.named_scope(SCOPE_ATTN):
            s = jnp.einsum("bqw,bkw->bqk", q2, rows,
                           preferred_element_type=f32, precision=precision)
            seen = held[:, None, :] & \
                (kpos[None, None, :] <= q_pos[:, :, None])    # [B, T, K]
            seen = jnp.repeat(seen, h, axis=1)                # [B, T H, K]
            s = jnp.where(seen, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            l = alpha * l + p.sum(-1, keepdims=True)
            acc = alpha * acc + jnp.einsum(
                "bqk,bkc->bqc", p.astype(q.dtype), rows[..., :rank],
                preferred_element_type=f32, precision=precision)
        return m_new, l, acc

    rows_q = (b, t * h, 1)
    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, block,
        (jnp.full(rows_q, NEG_INF, f32), jnp.zeros(rows_q, f32),
         jnp.zeros((b, t * h, rank), f32)))
    with jax.named_scope(SCOPE_ATTN):
        out = acc / jnp.where(l > 0, l, 1.0)
        return out.astype(q.dtype).reshape(b, t, h, rank)


def _page_copies(tables_ref, pool_hbm, buf, sem, s, li, n_pages):
    """The page walk both kernels share: `for_pages_of(blk, slot,
    what)` hands `what` one copy a page of compute block `blk` of slot
    `s`'s first `n_pages` pages (through its table row, out of layer
    `li` of the pool where it lies in HBM) into half `slot` of `buf`
    [2, pages a block, page, lanes]: `start()` them when the block
    before is being computed, `wait()` for them when it is their
    turn."""
    npb = buf.shape[1]

    def for_pages_of(blk, slot, what):
        def one(i, carry):
            phys = tables_ref[s, blk * npb + i]
            what(pltpu.make_async_copy(pool_hbm.at[li, phys],
                                       buf.at[slot, i], sem.at[slot]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(n_pages - blk * npb, npb), one, 0)
    return for_pages_of


def _kernel(li_ref, tables_ref, lens_ref, q_ref, pool_hbm, o_ref, buf, sem,
            m_ref, l_ref, acc_ref, *, rank, precision):
    s = pl.program_id(0)
    _, npb, page, lanes = buf.shape
    keys = npb * page
    length = lens_ref[s]
    n_pages = (length + page - 1) // page
    n_blocks = (n_pages + npb - 1) // npb
    for_pages_of = _page_copies(tables_ref, pool_hbm, buf, sem, s,
                                li_ref[0], n_pages)

    @pl.when(length == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(length > 0)
    def _():
        for_pages_of(0, 0, lambda copy: copy.start())
        q = q_ref[0]                                          # [H, lanes]
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        def block(blk, carry):
            slot = blk % 2

            @pl.when(blk + 1 < n_blocks)
            def _():
                for_pages_of(blk + 1, 1 - slot, lambda copy: copy.start())

            for_pages_of(blk, slot, lambda copy: copy.wait())
            rows = buf[slot].reshape(keys, lanes)
            # rows past the slot's length (the tail of its last page,
            # pages of this block that were not copied) hold anything:
            # only the walk's last block has such rows
            at = blk * keys + jax.lax.broadcasted_iota(
                jnp.int32, (keys, 1), 0)
            rows = jax.lax.cond(
                blk + 1 < n_blocks, lambda r: r,
                lambda r: jnp.where(at < length, r, jnp.zeros((), r.dtype)),
                rows).astype(q.dtype)
            scores = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=f32, precision=precision)
            kpos = blk * keys + jax.lax.broadcasted_iota(
                jnp.int32, (1, keys), 1)
            scores = jnp.where(kpos < length, scores, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new[:, :1])
            l_ref[...] = alpha * l_ref[...] + \
                jnp.sum(p, axis=1, keepdims=True)
            m_ref[...] = m_new
            acc_ref[...] = alpha[:, :1] * acc_ref[...] + jax.lax.dot_general(
                p.astype(q.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
                preferred_element_type=f32, precision=precision)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)
        o_ref[0] = (acc_ref[...] / l_ref[...][:, :1]).astype(o_ref.dtype)


def _geometry(pool, columns, w, rank, interpret):
    """(page, lanes, pages a compute block, interpret) of a kernel over
    `pool` through tables of `columns` columns for rows of `w` values,
    the first `rank` the values; raises where Mosaic could not copy
    and slice the pages by whole tiles."""
    _, _, page, lanes = pool.shape
    if lanes < w or lanes % LANE or rank > w:
        raise ValueError(
            f"a pool of {lanes} lanes does not hold rows of {w} values "
            f"(the first {rank} of them the values)")
    if interpret is None:
        interpret = not _on_tpu()
    sublanes = 8 * 4 // pool.dtype.itemsize
    if not interpret and (page % sublanes or rank % LANE):
        raise ValueError(
            f"a page of {page} tokens of {pool.dtype} with values of "
            f"{rank} lanes cannot be copied and sliced by whole tiles "
            f"({sublanes} rows, {LANE} lanes)")
    return page, lanes, block_pages(columns, page), interpret


def latent_decode_attention(q, pool, li, tables, lens, rank,
                            interpret=None):
    """q [B, H, W] (ONE scaled query row a slot, at position
    lens[b] - 1) against layer `li` of pool [L, P, page, lanes]
    through tables [B, max_pages]: every key below lens[b] is seen.
    Returns [B, H, rank] in q's type; a slot of length 0 reads nothing
    and returns zeros. `interpret` None: the Pallas interpreter
    wherever the backend is not a TPU."""
    b, h, w = q.shape
    page, lanes, npb, interpret = _geometry(
        pool, tables.shape[1], w, rank, interpret)
    kernel = functools.partial(
        _kernel, rank=rank,
        precision=jax.lax.Precision.HIGHEST if q.dtype == f32 else None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, lanes), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, rank), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, npb, page, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((h, LANE), f32),
            pltpu.VMEM((h, LANE), f32),
            pltpu.VMEM((h, rank), f32),
        ])
    return pl.pallas_call(
        kernel,
        name="latent_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(li, (1,)).astype(jnp.int32), tables.astype(jnp.int32),
      lens.astype(jnp.int32),
      jnp.pad(q, ((0, 0), (0, 0), (0, lanes - w))), pool)


def tile_tokens(t, h):
    """Tokens a query tile of the prefill kernel: `_TILE_ROWS` rows of
    `h` heads each, no more than the chunk's `t` tokens rounded up to
    the sublanes of a packed tile."""
    return max(1, min(_TILE_ROWS // h, -(-t // 16) * 16))


def tile_walk(low, high, length, page, npb):
    """What a query tile of the prefill kernel whose tokens stand at
    positions `low` .. `high` walks of a slot of `length` keys, in
    pages of `page` keys and compute blocks of `npb` pages: (the keys
    it walks, the pages it copies, the blocks they lie in, the first
    of them that are walked WHOLE). It walks as far as its last
    token's position and never past the length, and not at all where
    every token stands at or past the length (a chunk's pad rows); a
    block is walked whole, and unmasked, where every row of the tile
    sees every key of it, the others a page at a time. The kernel's
    own trip counts, and on host numbers their reckoning."""
    extent = jnp.where(low < length, jnp.minimum(high + 1, length), 0)
    n_pages = (extent + page - 1) // page
    whole = jnp.minimum(low + 1, length) // (npb * page)
    return extent, n_pages, (n_pages + npb - 1) // npb, whole


def _across(x, n):
    """x [rows, LANE], a row's statistic on every lane, as [rows, n]."""
    if n % LANE:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if n == LANE else pltpu.repeat(x, n // LANE, axis=1)


def _prefill_kernel(li_ref, tables_ref, lens_ref, pos_ref, q_at_ref, q_ref,
                    pool_hbm, o_ref, buf, sem, m_ref, l_ref, acc_ref, *,
                    heads, rank, precision):
    s, i = pl.program_id(0), pl.program_id(1)
    _, npb, page, lanes = buf.shape
    keys = npb * page
    tile = q_ref.shape[1]
    tq = tile // heads
    length = lens_ref[s]

    # the tile's tokens: the earliest and the last of their positions,
    # and the position a query row (token-major rows, `heads` a token)
    def span(j, carry):
        pos = pos_ref[s, i * tq + j]
        return jnp.minimum(carry[0], pos), jnp.maximum(carry[1], pos)

    pos0 = pos_ref[s, i * tq]
    low, high = jax.lax.fori_loop(1, tq, span, (pos0, pos0))
    mine = jax.lax.broadcasted_iota(jnp.int32, (tile, tq), 0) // heads == \
        jax.lax.broadcasted_iota(jnp.int32, (tile, tq), 1)
    q_at = jnp.sum(jnp.where(mine, q_at_ref[0, 0], 0), axis=1,
                   keepdims=True)                             # [tile, 1]
    extent, n_pages, n_blocks, whole = tile_walk(low, high, length, page,
                                                 npb)
    for_pages_of = _page_copies(tables_ref, pool_hbm, buf, sem, s,
                                li_ref[0], n_pages)

    @pl.when(extent == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def attend(q, rows, scores):
        """One step of the online softmax: float32 `scores` [tile, k]
        of `q` against the k `rows`, keys and values at once."""
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - _across(m_new, scores.shape[1]))
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = _across(alpha, rank) * acc_ref[...] + \
            jax.lax.dot_general(
                p.astype(q.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
                preferred_element_type=f32, precision=precision)

    def scores_of(q, rows):
        return jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=f32, precision=precision)

    @pl.when(extent > 0)
    def _():
        for_pages_of(0, 0, lambda copy: copy.start())
        upper = jnp.minimum(q_at, length - 1)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        def block(blk, carry):
            slot = blk % 2

            @pl.when(blk + 1 < n_blocks)
            def _():
                for_pages_of(blk + 1, 1 - slot, lambda copy: copy.start())

            for_pages_of(blk, slot, lambda copy: copy.wait())
            q = q_ref[0]                                      # [tile, lanes]

            @pl.when(blk < whole)
            def _():
                rows = buf[slot].reshape(keys, lanes).astype(q.dtype)
                attend(q, rows, scores_of(q, rows))

            # a block that holds a position of the tile's own tokens,
            # or the slot's last key: the pages that were copied, one
            # at a time, a key hidden from the rows that stand before
            # it; rows past the walk's extent (the tail of its last
            # page) hold anything
            @pl.when(blk >= whole)
            def _():
                def one(j, carry):
                    first = (blk * npb + j) * page
                    at = first + jax.lax.broadcasted_iota(
                        jnp.int32, (page, 1), 0)
                    rows = buf[slot, j]
                    rows = jnp.where(at < extent, rows,
                                     jnp.zeros((), rows.dtype)).astype(q.dtype)
                    kpos = first + jax.lax.broadcasted_iota(
                        jnp.int32, (1, page), 1)
                    attend(q, rows, jnp.where(kpos <= upper,
                                              scores_of(q, rows), NEG_INF))
                    return carry
                jax.lax.fori_loop(
                    0, jnp.minimum(n_pages - blk * npb, npb), one, 0)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)
        out = acc_ref[...] * _across(1.0 / l_ref[...], rank)
        # a chunk's pad rows, in a tile that holds a request's too
        o_ref[0] = jnp.where(q_at < length, out, 0.0).astype(o_ref.dtype)


def latent_prefill_attention(q, pool, li, tables, q_pos, lens, rank,
                             interpret=None):
    """`latent_attention`'s arguments and numbers for a prefill chunk,
    as a Pallas TPU kernel: q [B, T, H, W] (scaled) against layer `li`
    of pool [L, P, page, lanes] through tables [B, max_pages], row
    (b, t) at position q_pos[b, t] seeing keys at positions <= it and
    < lens[b]. Returns [B, T, H, rank] in q's type; zeros for a slot of
    length 0 and for rows at or past their slot's length (a chunk's
    pad rows, which the XLA form lets attend to the whole slot and the
    caller drops).

    One call. The query rows lie token-major, [T * H, lanes], and a
    tile of `_TILE_ROWS` of them (16 consecutive tokens of 64 heads)
    is one grid step. It walks the slot's pages where they lie in HBM,
    `_BLOCK_KEYS` keys to a compute block (a page a DMA,
    double-buffered: `_page_copies`), as far as ITS OWN last token's
    position and never past `lens[b]` (`tile_walk`); the block is the
    keys and, its first `rank` lanes, the values. Scores,
    probabilities, the running statistics and the accumulator live in
    VMEM: nothing of heads x rows x keys is written out. A block whose
    every key every row of the tile sees is one unmasked step; the
    block that holds the tile's own positions, or the slot's last key,
    is walked a page at a time under the mask, as far as the tile's
    last position: of a chunk's own 512 keys a tile attends to the
    pages up to its own, 320 on average. The online softmax is the XLA
    form's over the same blocks: float32 scores and statistics, `p`
    rounded to q's type before the product with the values; what a
    tile skips would have weighed an exact 0. `interpret` None: the
    Pallas interpreter wherever the backend is not a TPU."""
    b, t, h, w = q.shape
    page, lanes, npb, interpret = _geometry(
        pool, tables.shape[1], w, rank, interpret)
    tq = tile_tokens(t, h)
    pad = -t % tq
    tile = tq * h
    # pad tokens stand at the last token's position: their tile walks
    # no further for them, and their rows are cut off again
    q_pos = jnp.pad(q_pos.astype(jnp.int32), ((0, 0), (0, pad)), mode="edge")
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, lanes - w)))
    kernel = functools.partial(
        _prefill_kernel, heads=h, rank=rank,
        precision=jax.lax.Precision.HIGHEST if q.dtype == f32 else None)
    tiles = (t + pad) // tq
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, tiles),
        in_specs=[pl.BlockSpec((1, 1, 1, tq), lambda s, i, *_: (s, i, 0, 0)),
                  pl.BlockSpec((1, tile, lanes), lambda s, i, *_: (s, i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tile, rank), lambda s, i, *_: (s, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, npb, page, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((tile, LANE), f32),
            pltpu.VMEM((tile, LANE), f32),
            pltpu.VMEM((tile, rank), f32),
        ])
    # the positions go in twice: as scalars for a tile's trip counts,
    # and a tile's own on the lanes for its rows' mask
    out = pl.pallas_call(
        kernel,
        name="latent_prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, (t + pad) * h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(li, (1,)).astype(jnp.int32), tables.astype(jnp.int32),
      lens.astype(jnp.int32), q_pos, q_pos.reshape(b, tiles, 1, tq),
      q.reshape(b, (t + pad) * h, lanes), pool)
    return out.reshape(b, t + pad, h, rank)[:, :t]
