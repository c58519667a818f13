"""Differential attention (Ye et al., arXiv:2410.05258) of one query row
per slot against a paged K/V cache, read where the pages lie (Pallas
TPU kernel), and its XLA form.

Query heads come in pairs (2j, 2j + 1) and key/value heads in pairs
(2p, 2p + 1); query pair j reads key/value pair p = j // 2. Inside a
pair the first query head attends against the first key head and the
second against the second, and BOTH take the pair's two value heads
side by side, a value row of 2 d:

    a_h = softmax_causal(q_h k_{2 (h // 4) + h % 2}^T / sqrt(d))
              [v_{2 (h // 4)} ; v_{2 (h // 4) + 1}]             [2 d]

which is what this module gives back, a [.., H, 2 d]; the caller
subtracts a pair's two rows and norms (`models/phi4flash.py`).

The kernel is `paged_decode_attention`'s walk (that module has it at
length: a slot's table walked up to its length, a page one DMA,
double-buffered, heads never split out of the lanes, online softmax in
float32; `first` and `ring` for a layer that keeps a window's pages
only), with two differences. The indicator of the scores keeps, in row
m Hk + kh (m the query pair's index inside its key/value pair), the
lanes of key head kh from query row m; and the indicator of the values
keeps the lanes of kh's PAIR, 2 d of them. So a page's K and V are read
once for both softmaxes of every pair. The pools may belong to a layer
that wrote them for other layers to read: nothing is written here.

Off a TPU the XLA form (`usable`): the slot's pages gathered through
its table and `diff_attention` over them.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import NEG_INF, _on_tpu
from deepspeed_tpu.ops.transformer.paged_decode_attention import (
    LANE, _BLOCK_KEYS, _VMEM_LIMIT, padded_lanes)

f32 = jnp.float32


def usable():
    """Whether decode attends through the kernel (a TPU); the XLA form
    elsewhere (a test patches this to run the kernel interpreted)."""
    return _on_tpu()


def diff_attention(q, k, v, seen):
    """The XLA form. q [B, T, H, d]; k, v [B, Tk, Hk, d] with Hk =
    H / 2; `seen` [B, T, Tk] bool, the keys a query row sees (a row
    that sees none gets the mean of the values: mask it yourself).
    Returns a [B, T, H, 2 d] in v's type; softmax in float32."""
    b, t, h, d = q.shape
    tk, hk = k.shape[1], k.shape[2]
    qp = q.reshape(b, t, hk // 2, 2, 2, d)          # pair p, member m, i
    kp = k.reshape(b, tk, hk // 2, 2, d)            # pair p, i
    vp = v.reshape(b, tk, hk // 2, 2 * d)
    scores = jnp.einsum("btpmid,bspid->bpmits", qp, kp,
                        preferred_element_type=f32) / np.sqrt(d)
    scores = jnp.where(seen[:, None, None, None], scores, f32(-1e30))
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    a = jnp.einsum("bpmits,bspe->btpmie", p, vp)
    return a.reshape(b, t, h, 2 * d)


def _kernel(li_ref, tables_ref, lens_ref, qpos_ref, *refs, n_head,
            n_kv_head, head_dim, sm_scale, precision, windowed, ring):
    first_ref = refs[0] if windowed else None
    (q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_ref, l_ref,
     acc_ref) = refs[1:] if windowed else refs
    s = pl.program_id(0)
    group = n_head // n_kv_head
    lanes = q_ref.shape[2]
    hp = acc_ref.shape[0]
    wide = o_ref.shape[2]                 # a pair's values: 2 d
    _, npb, page, _ = kbuf.shape
    bk = npb * page
    length = lens_ref[s]
    n_pages = (length + page - 1) // page
    page0 = key0 = 0
    if windowed:
        page0 = first_ref[s] // page
        key0 = page0 * page
        n_pages = n_pages - page0
    n_blocks = (n_pages + npb - 1) // npb
    li = li_ref[0]

    def copies(blk, slot, i):
        column = page0 + blk * npb + i
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
        row = jax.lax.broadcasted_iota(jnp.int32, (hp, lanes), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (hp, lanes), 1)
        kh = row % n_kv_head
        real = row < n_head
        scored = real & (lane >= kh * head_dim) & (lane < (kh + 1) * head_dim)
        rows = [jnp.where(scored & (row // n_kv_head == j),
                          q_ref[0, j:j + 1, :], 0.0) for j in range(group)]
        qbd = sum(rows[1:], rows[0]).astype(kbuf.dtype)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        def block(blk, carry):
            slot = blk % 2

            @pl.when(blk + 1 < n_blocks)
            def _():
                for_pages_of(blk + 1, 1 - slot, lambda copy: copy.start())

            for_pages_of(blk, slot, lambda copy: copy.wait())
            k = kbuf[slot].reshape(bk, lanes)
            v = vbuf[slot].reshape(bk, lanes)
            at = key0 + blk * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0)
            v = jnp.where(at < length, v, jnp.zeros((), v.dtype))
            kpos = key0 + blk * bk + jax.lax.broadcasted_iota(
                jnp.int32, (1, bk), 1)
            scores = jax.lax.dot_general(
                qbd, k, (((1,), (1,)), ((), ())),
                preferred_element_type=f32, precision=precision) * sm_scale
            seen = (kpos <= qpos_ref[s]) & (kpos < length)
            if windowed:
                seen = seen & (kpos >= first_ref[s])
            scores = jnp.where(seen, scores, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new[:, :1])
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                      keepdims=True)
            m_ref[...] = m_new
            acc_ref[...] = alpha[:, :1] * acc_ref[...] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=f32, precision=precision)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)
        heads = acc_ref[...] / l_ref[...][:, :1]
        pair = (jax.lax.broadcasted_iota(jnp.int32, (hp, wide), 0)
                % n_kv_head) // 2
        out = jnp.zeros((hp, wide), f32)
        for t in range(n_kv_head // 2):
            out = out + jnp.where(pair == t,
                                  heads[:, t * wide:(t + 1) * wide], 0.0)
        o_ref[0] = out


def diff_decode_attention(q, k_pool, v_pool, li, tables, q_pos, lens, n_head,
                          first=None, ring=None, interpret=None,
                          name="diff_decode_attention"):
    """q [B, H d], one row a slot, against layer `li` of the page pools
    [L, P, page, lanes] (Hk = H / 2 heads of d a token on the lanes)
    through the page tables [B, max_pages]. Slot b's row sits at
    position q_pos[b] and sees keys at positions <= it, >= first[b]
    where `first` is given, and < lens[b], the slot's live length (0:
    not live; zeros, nothing read). `ring`: as `paged_decode_attention`.
    Returns a [B, H, 2 d] in q's type (the module's docstring).
    `interpret`: the kernel in the Pallas interpreter (tests);
    default: the kernel on a TPU, the XLA form elsewhere. `name`: the
    kernel's in a device profile."""
    if ring is not None and first is None:
        raise ValueError("a ring of pages needs the first visible key")
    b, c = q.shape
    n_kv_head = n_head // 2
    d = c // n_head
    _, _, page, lanes = k_pool.shape
    if lanes != padded_lanes(n_kv_head * d) or v_pool.shape != k_pool.shape \
            or n_head % 4:
        raise ValueError(
            f"pools {k_pool.shape} / {v_pool.shape} do not hold "
            f"{n_kv_head} heads of {d} for {n_head} query heads in pairs")
    if interpret is None and not usable():
        return _gathered(q, k_pool, v_pool, li, tables, q_pos, lens, n_head,
                         first, ring)
    wide = 2 * d
    if not interpret and (wide % LANE or page % 16):
        raise ValueError(
            f"a pair's values ({wide}) are not whole lane tiles, or a page "
            f"of {page} tokens not whole sublane tiles")
    # member m's row: on key head 2 p + i's lanes, query head 4 p + 2 m + i
    qm = q.reshape(b, n_kv_head // 2, 2, 2, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, 2, n_kv_head * d)
    qm = jnp.pad(qm, ((0, 0), (0, 0), (0, lanes - n_kv_head * d))).astype(f32)
    dtype = k_pool.dtype
    hp = -(-n_head // 16) * 16
    npb = max(1, _BLOCK_KEYS // page)
    kernel = functools.partial(
        _kernel, n_head=n_head, n_kv_head=n_kv_head, head_dim=d,
        sm_scale=1.0 / np.sqrt(d),
        precision=jax.lax.Precision.HIGHEST if dtype == f32 else None,
        windowed=first is not None, ring=ring)
    row = lambda s, *_: (s, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if first is None else 5,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, 2, lanes), row),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, hp, wide), row),
        scratch_shapes=[
            pltpu.VMEM((2, npb, page, lanes), dtype),
            pltpu.VMEM((2, npb, page, lanes), dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((hp, LANE), f32),
            pltpu.VMEM((hp, LANE), f32),
            pltpu.VMEM((hp, lanes), f32),
        ])
    i32 = lambda x: x.astype(jnp.int32)
    out = pl.pallas_call(
        kernel,
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hp, wide), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=bool(interpret),
    )(i32(jnp.reshape(li, (1,))), i32(tables), i32(lens), i32(q_pos),
      *(() if first is None else (i32(first),)), qm, k_pool, v_pool)
    # row m Hk + 2 p + i -> head 4 p + 2 m + i
    out = out[:, :n_head].reshape(b, 2, n_kv_head // 2, 2, wide)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, n_head, wide) \
        .astype(q.dtype)


def key_positions(last, cols, page, ring=None):
    """[B, cols * page] the position of every key of a gathered table
    row, for slots whose last key is `last` [B]: column c holds page c,
    or in a ring of `ring` columns the page at or below the last one
    that is congruent to c (released or never held: its keys lie below
    every query's first, or below 0)."""
    held = jnp.broadcast_to(jnp.arange(cols)[None, :], (last.shape[0], cols))
    if ring:
        top = (last // page)[:, None]
        held = top - (top - held) % ring
    return (held[:, :, None] * page +
            jnp.arange(page)[None, None, :]).reshape(last.shape[0], -1)


def _gathered(q, k_pool, v_pool, li, tables, q_pos, lens, n_head, first,
              ring):
    """`diff_decode_attention` in XLA: every slot's table row gathered
    whole."""
    b, c = q.shape
    hk = n_head // 2
    d = c // n_head
    page = k_pool.shape[2]
    cols = tables.shape[1]
    rows = lambda pool: pool[li][tables][..., :hk * d].reshape(
        b, cols * page, hk, d)
    kpos = key_positions(lens - 1, cols, page, ring)
    seen = (kpos >= 0) & (kpos <= q_pos[:, None]) & (kpos < lens[:, None])
    if first is not None:
        seen = seen & (kpos >= first[:, None])
    v = jnp.where(seen[:, :, None, None], rows(v_pool), 0)
    a = diff_attention(q.reshape(b, 1, n_head, d), rows(k_pool), v,
                       seen[:, None, :])[:, 0]
    return jnp.where((lens > 0)[:, None, None], a, 0).astype(q.dtype)
