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

Two forms of the same numbers:

  * `latent_attention`: XLA. The slots' pages are gathered through
    their tables a block of `BLOCK_PAGES` pages at a time, as far as
    the longest slot reaches and no further (a loop of a dynamic trip
    count), under an online softmax in float32. A prefill chunk
    attends through it (one slot, a few hundred query rows of 64
    heads: a matrix-unit problem), and it is the decode kernel's
    oracle and the path a backend without Mosaic takes.
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


def usable():
    """Whether decode attends through the Mosaic kernel (a TPU) or
    through the XLA form."""
    return _on_tpu()


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


def _kernel(li_ref, tables_ref, lens_ref, q_ref, pool_hbm, o_ref, buf, sem,
            m_ref, l_ref, acc_ref, *, rank, precision):
    s = pl.program_id(0)
    _, npb, page, lanes = buf.shape
    keys = npb * page
    length = lens_ref[s]
    n_pages = (length + page - 1) // page
    n_blocks = (n_pages + npb - 1) // npb
    li = li_ref[0]

    def for_pages_of(blk, slot, what):
        def one(i, carry):
            phys = tables_ref[s, blk * npb + i]
            what(pltpu.make_async_copy(pool_hbm.at[li, phys],
                                       buf.at[slot, i], sem.at[slot]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(n_pages - blk * npb, npb), one, 0)

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


def latent_decode_attention(q, pool, li, tables, lens, rank,
                            interpret=None):
    """q [B, H, W] (ONE scaled query row a slot, at position
    lens[b] - 1) against layer `li` of pool [L, P, page, lanes]
    through tables [B, max_pages]: every key below lens[b] is seen.
    Returns [B, H, rank] in q's type; a slot of length 0 reads nothing
    and returns zeros. `interpret` None: the Pallas interpreter
    wherever the backend is not a TPU."""
    b, h, w = q.shape
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
    npb = max(1, _BLOCK_KEYS // page)
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
