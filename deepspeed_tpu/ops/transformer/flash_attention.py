"""Flash attention as a Pallas TPU kernel.

TPU-native replacement for the reference's fused CUDA attention chain
(`csrc/transformer/softmax_kernels.cu`, `strided_batch_gemm.h`,
`ds_transformer_cuda.cpp:1026-1044`): instead of materializing the
[B, H, T, T] score tensor in HBM, the kernel streams K/V blocks through
VMEM with an online-softmax running (m, l) pair, so HBM traffic is
O(T·d) and the MXU sees back-to-back [block_q, d]×[d, block_k] matmuls.
Backward is the standard two-kernel flash backward (dKV sweep + dQ
sweep) off saved logsumexp rows — the reference instead checkpoints 17
intermediate activations (`ops/transformer/transformer.py:155-213`).

Layout.  ONE layout, the projections' own: every operand and result of
a launch is a `[B, T, H·D]` array (the free reshape of the public
`[B, T, H, D]`), read and written in COLUMN TILES of 128 lanes (two
heads of 64 side by side, which is what `[B, T, H, 64]` already is in
memory) or of D lanes (one head, D a multiple of 128).  The grid walks
(batch group, T block, column tile, T block); a block is
`(G, block, tile)` at `(b, qi | ki, j)`, in the tiled HBM layout a run
of whole tiles.  Nothing is transposed, paired or unpaired outside the
kernel.  Three things follow from the shape alone:

  * where H·D is a whole number of tiles the caller may hand over the
    `c_attn` product `[B, T, 3·H·D]` whole (`flash_attention_qkv`): q,
    k and v are then column tiles j, n + j and 2n + j of ONE operand
    and the split costs no copy, forward or backward;
  * where it is not (H odd at D = 64: GPT-2 1.5B's 25 heads) the last
    tile holds one head. Its out-of-range lanes are undefined on the
    way in, so the kernel zeroes them before any contraction, and
    dropped by the edge block's write on the way out;
  * the row statistics (lse, δ) are `[B, T, H]` float32, the layout in
    which XLA's rowsum(dO ⊙ out) comes out. A launch reads the block
    `(G, block_q, H)` and picks its tile's one or two columns with a
    lane select; the forward, whose column tiles of one T block follow
    each other, writes its columns into the block the same way.

Head packing (d = 64) is a choice INSIDE the kernel, on the same tile.
The MXU contracts 128 elements per pass, so two d = 64 heads worked in
turn run QK^T at K=64 (half the systolic rows idle) and PV at N=64
(half the lanes idle).  With `head_packing` the kernel works the tile
whole:

    Qp  = [q0 | q1]                          [bq, 128]   (the tile)
    Kbd = [[k0 | 0], [0 | k1]]               [2·bk, 128] (block diagonal)
    S   = Qp · Kbdᵀ = [S0 | S1]              [bq, 2·bk]  K=128 contraction
    O   = P · Vbd   = [O0 | O1]              [bq, 128]   N=128 lanes

The zero blocks double the MAC count per useful flop, but every matmul
now runs at full MXU occupancy — a win whenever K=64 throughput is
below half of K=128 throughput.  The zero lanes contribute exact +0
to every fp32 partial sum, so packed and
unpacked results agree bit-for-bit under a deterministic backend.  The
backward's dV/dK contractions come out row-stacked ([2·bk, 128] with
the useful blocks on the diagonal) and are folded back with a lane
select.  `head_packing="auto"` packs on real TPU for d=64; the CPU
interpreter path, d ≠ 64, and `"off"` work the tile's heads in turn.

Ring-attention partial merge.  `flash_attention_merge` fuses the ring
step's (out, lse) softmax-partial merge into the kernel epilogue: the
previous partial rides in as two extra refs and the merged result is
written directly, so the per-step partial never round-trips HBM through
an XLA elementwise merge chain (`ops/sequence/ring_attention.py`).
(Its interface, and `flash_attention_with_lse`'s, keeps lse as
`[B, H, T, 1]`: those two re-lay B·H·T floats at their boundary.)

On non-TPU backends the same kernels run in Pallas interpreter mode so
CPU CI validates kernel logic bit-for-bit against the XLA reference path.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.per_device import (COLS, ROWS, divides_cols,
                                          per_device)

NEG_INF = -1e30
# The online softmax runs in log2 space: exp2 is the TPU VPU's native
# transcendental (jnp.exp lowers to exp2(x·log2e) anyway), so folding
# log2e into the QK^T scale removes one vmul per score element per
# pass — the softmax VPU chain is a first-order term at d=64, where
# the MXU work per score element is small. LSE is saved in log2 space;
# both backward kernels consume it there.
LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453
# Block sizes swept on v5e at the flagship shape (B8 T1024 H25 d64,
# round 4): 1024/1024 beats 512/512 by ~3.5% fwd+bwd and — decisively —
# makes T<=1024 a SINGLE tile, which routes the backward through the
# fused one-pass kernel below (no second s/p/dp recompute sweep). For
# longer T the per-call min(block, T) keeps tiles at 1024.
_DEFAULT_BLOCK = 1024
# Heads processed per grid step.  At short T the grid is overhead-bound
# (each step's matmuls are microseconds), so batching heads into one
# step cuts the iteration count G-fold; VMEM cost is G * block_q *
# block_k fp32 for the score tile (the pallas calls raise the Mosaic
# scoped-vmem ceiling to make the fatter tiles legal).
_DEFAULT_HEAD_GROUP = 8
_VMEM_LIMIT = 100 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _on_tpu():
    return jax.default_backend() == "tpu"


def dense_attention(q, k, v, mask=None, causal=False, sm_scale=None,
                    dropout_rate=0.0, dropout_rng=None, deterministic=True):
    """Dense XLA attention over [B, T, H, D] — the reference path for the
    flash kernel and the fallback when dropout/masks rule it out.
    fp32 softmax; `mask` is additive, broadcastable to [B, H, Tq, Tk]."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores * sm_scale
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        tri = jnp.tril(jnp.ones((t_q, t_k), dtype=bool))
        scores = jnp.where(tri[None, None, :, :], scores, jnp.float32(-1e30))
    if mask is not None:
        scores = scores + mask.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    if not deterministic and dropout_rate > 0.0:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                    probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _fit_block(block, t):
    """Largest power-of-two shrink of `block` (floor 128) that divides
    t, after clamping to t — so T=1536 gets 512-wide tiles instead of
    failing the 1024 default."""
    block = min(block, t)
    while block > 128 and t % block:
        block //= 2
    return block


def flash_attention_usable(q, no_dropout: bool,
                           block_q=None, block_k=None):
    """The kernel handles [B, T, H, D] with T divisible by the block size
    and D = 64 (two heads a column tile) or a multiple of 128 (one);
    dropout stays on the XLA path."""
    if not no_dropout:
        return False
    if q.ndim != 4:
        return False
    t, d = q.shape[1], q.shape[3]
    block_q = _fit_block(block_q or _DEFAULT_BLOCK, t)
    block_k = _fit_block(block_k or _DEFAULT_BLOCK, t)
    # t % 128 guards the lane dimension: _fit_block clamps the block to
    # t for 128 <= t < 1024, so without it a T like 136 would "fit" its
    # own single tile — unaligned lanes Mosaic rejects or pads on real
    # TPU (CPU interpret mode hides it).
    return t % block_q == 0 and t % block_k == 0 and \
        (d == 64 or d % 128 == 0) and t >= 128 and t % 128 == 0


def _resolve_head_packing(head_packing, d, interpret):
    """Head-packing mode -> bool.  "auto" works a d=64 column tile's two
    heads as one K=128 contraction on real TPU; the interpreter path
    works them in turn so CPU CI timings/VMEM budgets reflect the
    per-head kernel unless a test forces "packed".  An odd head count
    leaves one head in the last tile, NOT a fallback — the flagship's
    25 heads still pack."""
    if head_packing in ("off", False, 0):
        return False
    if head_packing in ("packed", True, 1):
        if d != 64:
            raise ValueError(
                f"head_packing='packed' requires head_dim 64 (got {d}): "
                "packing pairs two 64-wide heads into one K=128 "
                "contraction")
        return True
    if head_packing in ("auto", None):
        return d == 64 and not interpret
    raise ValueError(
        f"head_packing={head_packing!r}: expected 'auto', 'packed' or "
        "'off'")


# ----------------------------------------------------------------------
# what a kernel does with one column tile
# ----------------------------------------------------------------------
def _tile_width(d):
    """Lanes of one column tile: two heads of 64, or one of d."""
    return 128 if d == 64 else d


def _tile(ref, j, ncol, edge):
    """The block of `ref`. `edge` (static) is how many lanes of the LAST
    column tile lie inside the array, 0 where all do: past them an edge
    block holds whatever was in VMEM, and NaN × 0 in a block-diagonal
    contraction would reach the real head, so they are zeroed here."""
    x = ref[...]
    if not edge:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    keep = jnp.logical_or(j < ncol - 1, lane < edge)
    return jnp.where(keep, x, jnp.zeros_like(x))


def _tile_stats(ref, j, heads):
    """A [G, bq, H] block of row statistics -> the columns [G, bq, 1] of
    column tile j's `heads` heads (a lane select; a column past H, the
    odd head count's phantom, reads 0)."""
    x = ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return [jnp.sum(jnp.where(lane == heads * j + i, x, 0.0), axis=-1,
                    keepdims=True) for i in range(heads)]


def _store_tile_stats(ref, j, cols):
    """Write column tile j's statistics into its columns of the
    [G, bq, H] block, which stays in VMEM while the grid walks the
    column tiles of one T block."""
    x = ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    for i, col in enumerate(cols):
        x = jnp.where(lane == len(cols) * j + i, col, x)
    ref[...] = x


def _lanes(x, i, d):
    """Head i's d lanes of a tile."""
    return x[:, :, i * d:(i + 1) * d]


def _join(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def _block_diag_pack(x, half):
    """[G, n, 2h] -> [G, 2n, 2h] block-diagonal stack: rows [:n] keep
    the first head's lanes ([x0 | 0]), rows [n:] the second's
    ([0 | x1]).  The zero blocks are what buy the K=128 contraction;
    they contribute exact +0 to every fp32 partial sum."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    zero = jnp.zeros_like(x)
    top = jnp.where(lane < half, x, zero)
    bot = jnp.where(lane < half, zero, x)
    return jnp.concatenate([top, bot], axis=1)


def _block_diag_fold(x, half, n):
    """Fold a row-stacked [G, 2n, 2h] cross-product back to the packed
    [G, n, 2h] layout: the useful blocks sit on the block diagonal
    (top-left for head 0, bottom-right for head 1); the off-diagonal
    blocks are cross-head garbage the lane select drops."""
    top = x[:, :n]
    bot = x[:, n:]
    lane = jax.lax.broadcasted_iota(jnp.int32, top.shape, top.ndim - 1)
    return jnp.where(lane < half, top, bot)


def _halves(a, b, half):
    """Broadcast two per-head row stats [G, bq, 1] into the packed
    [G, bq, 2·half] lane layout (first half holds a, second b)."""
    shape = a.shape[:-1] + (half,)
    return jnp.concatenate([jnp.broadcast_to(a, shape),
                            jnp.broadcast_to(b, shape)], axis=-1)


def _two_cols(x, half):
    """A half-broadcast [G, bq, 2·half] stat's two representative
    columns, each [G, bq, 1]."""
    return [x[:, :, :1], x[:, :, half:half + 1]]


def _mask_causal(s, causal, qi, ki, block_q, block_k):
    """Apply the causal mask to a score block.

    Unconditional by design: gating the mask behind a value-returning
    `lax.cond` on "does this block straddle the diagonal" was measured
    SLOWER in the forward kernel (interleaved A/B on v5e at the
    flagship shape: up to +26% fwd) — Mosaic serializes around the
    branched tile and loses more than the iota/compare/select chain
    costs. Blocks fully above the diagonal never reach here (the
    `visible` guard skips their matmuls entirely)."""
    if not causal:
        return s
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where((rows >= cols)[None], s, NEG_INF)


def _mask_causal_packed(s, causal, qi, ki, block_q, block_k):
    """Causal mask over a packed [G, bq, 2·bk] score tile: columns
    [:bk] and [bk:] carry the SAME key positions (one per head), so the
    key index is the column index modulo bk."""
    if not causal:
        return s
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 2 * block_k), 0)
    col = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 2 * block_k), 1)
    key = ki * block_k + jnp.where(col >= block_k, col - block_k, col)
    return jnp.where((rows >= key)[None], s, NEG_INF)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
# Grid (batch group, q block, column tile, k block): K innermost so the
# (m, l, acc) scratch carries across K blocks, the column tile inside
# the q block so the block of lse stays put while its columns are
# written.
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal,
                block_q, block_k, merge, d, edge):
    """The tile's heads in turn, each at its own d lanes."""
    if merge:
        (po_ref, plse_ref, o_ref, lse_ref, lse_n_ref,
         m_scr, l_scr, acc_scr) = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi, j, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    ncol, nk = pl.num_programs(2), pl.num_programs(3)
    heads = q_ref.shape[-1] // d

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: a K block strictly above the diagonal contributes nothing —
    # skip its matmuls entirely (the grid still visits it).
    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        qt = _tile(q_ref, j, ncol, edge)          # [G, bq, tile] native
        kt = _tile(k_ref, j, ncol, edge)          # [G, bk, tile]
        vt = _tile(v_ref, j, ncol, edge)
        for i in range(heads):
            q, k, v = (_lanes(x, i, d) for x in (qt, kt, vt))
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * (sm_scale * LOG2E)
            s = _mask_causal(s, causal, qi, ki, block_q, block_k)

            m_prev = m_scr[i, :, :, :1]            # [G, bq, 1]
            l_prev = l_scr[i, :, :, :1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp2(s - m_new)                # [G, bq, bk]
            alpha = jnp.exp2(m_prev - m_new)       # [G, bq, 1]
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # [G, bq, d]
            acc_scr[i] = acc_scr[i] * alpha + pv
            m_scr[i, :, :, :1] = m_new
            l_scr[i, :, :, :1] = l_new

    @pl.when(ki == nk - 1)
    def _():
        if merge:
            plses = _tile_stats(plse_ref, j, heads)
            po = po_ref[...]
        outs, lses, lse_ns = [], [], []
        for i in range(heads):
            m = m_scr[i, :, :, :1]
            l = l_scr[i, :, :, :1]
            # log2-space LSE (= natural lse · log2e); consumed only by
            # the backward kernels, which stay in the same space
            lse_n = m + jnp.log2(l)
            if merge:
                # in-kernel softmax-partial merge: fold the previous
                # ring partial into this pass's (m, l, acc) before the
                # single HBM write (ops/sequence/ring_attention.py)
                plse = plses[i]                    # [G, bq, 1]
                mm = jnp.maximum(lse_n, plse)
                w_p = jnp.exp2(plse - mm)
                # w_n / l == exp2(m - mm): acc is unnormalized, so its
                # merge weight folds the 1/l normalization in
                wsum = w_p + jnp.exp2(lse_n - mm)
                outs.append((_lanes(po, i, d) * w_p +
                             acc_scr[i] * jnp.exp2(m - mm)) / wsum)
                lses.append(mm + jnp.log2(wsum))
                lse_ns.append(lse_n)
            else:
                outs.append(acc_scr[i] / l)
                lses.append(lse_n)
        o_ref[...] = _join(outs).astype(o_ref.dtype)
        _store_tile_stats(lse_ref, j, lses)
        if merge:
            _store_tile_stats(lse_n_ref, j, lse_ns)


def _fwd_kernel_packed(q_ref, k_ref, v_ref, *rest, sm_scale, causal,
                       block_q, block_k, merge, edge):
    """The tile's two heads at once: the QK^T contraction runs at K=128
    and PV at N=128 (see module docstring).  m/l scratch is
    half-broadcast-stored ([G, bq, 128] with each head's stat replicated
    across its 64 lanes) so alpha/l apply to the packed acc with plain
    elementwise ops."""
    if merge:
        (po_ref, plse_ref, o_ref, lse_ref, lse_n_ref,
         m_scr, l_scr, acc_scr) = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi, j, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    ncol, nk = pl.num_programs(2), pl.num_programs(3)
    half = q_ref.shape[-1] // 2

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        q = _tile(q_ref, j, ncol, edge)            # [G, bq, 128]
        k = _tile(k_ref, j, ncol, edge)            # [G, bk, 128]
        kbd = _block_diag_pack(k, half)            # [G, 2bk, 128]
        s = jax.lax.dot_general(
            q, kbd, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * (sm_scale * LOG2E)
        s = _mask_causal_packed(s, causal, qi, ki, block_q, block_k)

        s0 = s[:, :, :block_k]
        s1 = s[:, :, block_k:]
        m_prev = m_scr[...]                        # [G, bq, 128]
        l_prev = l_scr[...]
        m_cur = _halves(jnp.max(s0, axis=-1, keepdims=True),
                        jnp.max(s1, axis=-1, keepdims=True), half)
        m_new = jnp.maximum(m_prev, m_cur)
        p0 = jnp.exp2(s0 - m_new[:, :, :1])
        p1 = jnp.exp2(s1 - m_new[:, :, half:half + 1])
        alpha = jnp.exp2(m_prev - m_new)
        l_new = alpha * l_prev + _halves(
            jnp.sum(p0, axis=-1, keepdims=True),
            jnp.sum(p1, axis=-1, keepdims=True), half)

        v = _tile(v_ref, j, ncol, edge)
        vbd = _block_diag_pack(v, half)            # [G, 2bk, 128]
        p = jnp.concatenate([p0, p1], axis=-1)     # [G, bq, 2bk]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), vbd, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)    # [G, bq, 128]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _():
        m = m_scr[...]
        l = l_scr[...]
        lse_n = m + jnp.log2(l)                    # half-broadcast
        if merge:
            plse_b = _halves(*_tile_stats(plse_ref, j, 2), half)
            mm = jnp.maximum(lse_n, plse_b)
            w_p = jnp.exp2(plse_b - mm)
            wsum = w_p + jnp.exp2(lse_n - mm)
            out = (po_ref[...] * w_p +
                   acc_scr[...] * jnp.exp2(m - mm)) / wsum
            o_ref[...] = out.astype(o_ref.dtype)
            _store_tile_stats(lse_ref, j,
                              _two_cols(mm + jnp.log2(wsum), half))
            _store_tile_stats(lse_n_ref, j, _two_cols(lse_n, half))
        else:
            o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
            _store_tile_stats(lse_ref, j, _two_cols(lse_n, half))


def _head_group(b, block_q, score_k, tile_budget=8 * 1024 * 1024):
    """Largest group G (≤ default) of batch rows worked in one grid step
    on one column tile, dividing B, with the fp32 score tile
    [G, block_q, score_k] capped to `tile_budget` bytes of VMEM (the
    backward kernels keep ~4 score-sized tiles live, so they pass a
    smaller budget)."""
    g = _DEFAULT_HEAD_GROUP
    cap = max(1, tile_budget // (block_q * score_k * 4))
    g = min(g, cap)
    while b % g:
        g -= 1
    return max(g, 1)


# operand layouts, as per_device dims: the batch divides with the rows
# of the mesh, the heads (columns of [B, T, H·D], of [B, T, H]) with its
# columns: WHOLE heads, so every launcher hands per_device H beside the
# operands (`cols=`; m | H·D alone would cut a head in two). A shard
# with an odd count of d=64 heads has its own edge tile.
_BTC = (ROWS, None, COLS)
_WHOLE = (ROWS, None, None)


def _qkv_dims(qkv):
    return (_WHOLE,) if len(qkv) == 1 else (_BTC,) * 3


def _n_heads(qkv, d):
    """H of a launch's q, k, v (as `_columns` takes them)."""
    c = qkv[0].shape[-1]
    return (c if len(qkv) == 3 else c // 3) // d


def _columns(qkv, d):
    """Of a launch's q, k, v (three [B, T, C] arrays, or the one
    [B, T, 3·C] product): B, T, C, the tile width, the column tiles of
    C, and each of q, k, v as (array, its first column tile)."""
    b, t, c = qkv[0].shape
    w = _tile_width(d)
    if len(qkv) == 3:
        assert c % d == 0, (c, d)
        return b, t, c, w, pl.cdiv(c, w), [(x, 0) for x in qkv]
    c //= 3
    assert c % w == 0, (c, w)
    return b, t, c, w, c // w, [(qkv[0], i * (c // w)) for i in range(3)]


def _specs(g, block_q, block_k, w, h, q_axis, k_axis, col_axis):
    """BlockSpecs of a grid whose axis 0 walks the batch groups,
    `col_axis` the column tiles and `q_axis` / `k_axis` the T blocks of
    q and of k (None: the one block there is). Returns (at_q, at_k,
    stat): at_q(first) / at_k(first) the spec of a [B, T, ·] operand
    read from its column tile `first` on, stat that of a [B, T, H] row
    statistic."""
    def t_block(ids, axis):
        return 0 if axis is None else ids[axis]

    def tiles(block, axis):
        return lambda first=0: pl.BlockSpec(
            (g, block, w),
            lambda *ids: (ids[0], t_block(ids, axis), first + ids[col_axis]))

    stat = pl.BlockSpec((g, block_q, h),
                        lambda *ids: (ids[0], t_block(ids, q_axis), 0))
    return tiles(block_q, q_axis), tiles(block_k, k_axis), stat


def _fwd(qkv, d, sm_scale, causal, block_q, block_k, interpret, pack,
         prev=None):
    """Forward launcher over `qkv` (a tuple: q, k, v [B, T, C], or the
    [B, T, 3·C] product alone).  Returns (out [B, T, C], lse [B, T, H]);
    with `prev = (prev_out [B, T, C], prev_lse [B, T, H])` the kernel
    merges the prior softmax partial in its epilogue and additionally
    returns the CURRENT partial's lse_n [B, T, H] (the backward
    residual)."""
    merge = prev is not None
    local = functools.partial(
        _fwd_local, n_qkv=len(qkv), d=d, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret, pack=pack)
    return per_device(
        local,
        in_dims=_qkv_dims(qkv) + ((_BTC, _BTC) if merge else ()),
        out_dims=(_BTC,) * (3 if merge else 2), cols=(_n_heads(qkv, d),))(
            *qkv, *(prev if merge else ()))


def _fwd_local(*operands, n_qkv, d, sm_scale, causal, block_q, block_k,
               interpret, pack):
    """One device's launch."""
    qkv, prev = operands[:n_qkv], operands[n_qkv:]
    b, t, c, w, ncol, (q, k, v) = _columns(qkv, d)
    h = c // d
    heads = w // d
    merge = bool(prev)

    # 8 MB score-tile budget. A 24 MB budget (g=5 at the flagship
    # shape) measures ~20% faster on the ISOLATED kernel chain but ~1%
    # slower inside the full train step (VMEM pressure against the
    # surrounding fusions) — keep the in-model winner.  A d=64 tile
    # scores two heads, [bq, 2·bk] packed or twice [bq, bk] in turn.
    g = _head_group(b, block_q, heads * block_k)
    nq, nk = t // block_q, t // block_k
    grid = (b // g, nq, ncol, nk)
    static = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, merge=merge, edge=c % w)
    if pack:
        kernel = functools.partial(_fwd_kernel_packed, **static)
    else:
        kernel = functools.partial(_fwd_kernel, d=d, **static)

    q_spec, kv_spec, stat_spec = _specs(g, block_q, block_k, w, h, 1, 3, 2)
    stat_shape = jax.ShapeDtypeStruct((b, t, h), jnp.float32)
    in_specs = [q_spec(q[1]), kv_spec(k[1]), kv_spec(v[1])]
    operands = [q[0], k[0], v[0]]
    out_specs = [q_spec(), stat_spec]
    out_shape = [
        jax.ShapeDtypeStruct((b, t, c),
                             jnp.float32 if merge else q[0].dtype),
        stat_shape,
    ]
    if merge:
        in_specs += [q_spec(), stat_spec]
        operands += list(prev)
        out_specs.append(stat_spec)
        out_shape.append(stat_shape)
    lead = () if pack else (heads,)
    return tuple(pl.pallas_call(
        kernel,
        name="flash_fwd" + ("_packed" if pack else ""),
        grid=grid,
        compiler_params=_COMPILER_PARAMS,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM(lead + (g, block_q, 128), jnp.float32),
            pltpu.VMEM(lead + (g, block_q, 128), jnp.float32),
            pltpu.VMEM(lead + (g, block_q, w if pack else d),
                       jnp.float32),
        ],
        interpret=interpret,
    )(*operands))


# ----------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------
def _p_ds(q, k, v, do, lse, delta, sm_scale, causal, qi, ki, block_q,
          block_k):
    """One head's recomputed P and dS for a [G, bq, bk] tile."""
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * (sm_scale * LOG2E)
    s = _mask_causal(s, causal, qi, ki, block_q, block_k)
    p = jnp.exp2(s - lse)                          # [G, bq, bk]
    # dP = dO Vᵀ ; dS = P ⊙ (dP − δ) · scale
    dp = jax.lax.dot_general(
        do, v, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * sm_scale


def _bwd_refs(refs, has_out, has_delta):
    """A backward kernel's refs -> ((q, k, v, dO, out | None, lse,
    delta | None), the results and scratch that follow)."""
    refs = list(refs)
    out = refs.pop(4) if has_out else None
    delta = refs.pop(5) if has_delta else None
    return (*refs[:4], out, refs[4], delta), refs[5:]


def _deltas(do, out, delta_ref, j, heads, d):
    """δ of the tile's heads, each [G, bq, 1]: rowsum(dO ⊙ out) over the
    head's lanes where `out` is given, plus the head's column of the
    `delta` operand where that is given."""
    cols = [] if delta_ref is None else _tile_stats(delta_ref, j, heads)
    if out is None:
        return cols
    prod = do.astype(jnp.float32) * out.astype(jnp.float32)
    sums = [jnp.sum(_lanes(prod, i, d), axis=-1, keepdims=True)
            for i in range(heads)]
    return [a + b for a, b in zip(sums, cols)] if cols else sums


def _bwd_tiles(operands, j, ncol, edge, heads, d):
    """A backward kernel's operands (`_bwd_refs`) at column tile j: the
    tiles of q, k, v and dO, and its heads' columns of lse and of δ."""
    *tiles, out_ref, lse_ref, delta_ref = operands
    q, k, v, do = (_tile(r, j, ncol, edge) for r in tiles)
    out = None if out_ref is None else _tile(out_ref, j, ncol, edge)
    return (q, k, v, do, _tile_stats(lse_ref, j, heads),
            _deltas(do, out, delta_ref, j, heads, d))


def _heads_of(operands, j, ncol, edge, d):
    """`_bwd_tiles` head by head: a list of (q, k, v, dO: the head's
    lanes of each tile; lse, δ: its columns)."""
    heads = operands[0].shape[-1] // d
    *tiles, lse, delta = _bwd_tiles(operands, j, ncol, edge, heads, d)
    return [tuple(_lanes(x, i, d) for x in tiles) + (lse[i], delta[i])
            for i in range(heads)]


def _bwd_dkv_kernel(*refs, sm_scale, causal, block_q, block_k, d, edge,
                    has_out, has_delta):
    operands, (dk_ref, dv_ref, dk_scr, dv_scr) = _bwd_refs(
        refs, has_out, has_delta)
    ki, j, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    ncol, nq = pl.num_programs(2), pl.num_programs(3)

    @pl.when(qi == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        for i, (q, k, v, do, lse, delta) in enumerate(
                _heads_of(operands, j, ncol, edge, d)):
            p, ds = _p_ds(q, k, v, do, lse, delta, sm_scale, causal,
                          qi, ki, block_q, block_k)
            # dV += Pᵀ dO
            dv_scr[i] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            # dK += dSᵀ Q
            dk_scr[i] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _():
        heads = range(dk_scr.shape[0])
        dk_ref[...] = _join([dk_scr[i] for i in heads]).astype(dk_ref.dtype)
        dv_ref[...] = _join([dv_scr[i] for i in heads]).astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, d, edge,
                   has_out, has_delta):
    operands, (dq_ref, dq_scr) = _bwd_refs(refs, has_out, has_delta)
    qi, j, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    ncol, nk = pl.num_programs(2), pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        for i, (q, k, v, do, lse, delta) in enumerate(
                _heads_of(operands, j, ncol, edge, d)):
            _, ds = _p_ds(q, k, v, do, lse, delta, sm_scale, causal,
                          qi, ki, block_q, block_k)
            # dQ += dS K
            dq_scr[i] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[...] = _join([dq_scr[i] for i in range(dq_scr.shape[0])]) \
            .astype(dq_ref.dtype)


def _bwd_fused_kernel(*refs, sm_scale, causal, block_q, block_k, d, edge,
                      has_out, has_delta):
    """Single-tile backward (T == block): s, p and dP exist once, so
    dQ, dK and dV all come out of ONE pass — the two-kernel flash
    backward recomputes s/p (and dP) in each sweep, paying ~2x the
    matmul+exp work at tiles the VMEM can hold whole."""
    operands, (dq_ref, dk_ref, dv_ref) = _bwd_refs(refs, has_out, has_delta)
    j, ncol = pl.program_id(1), pl.num_programs(1)
    dqs, dks, dvs = [], [], []
    for q, k, v, do, lse, delta in _heads_of(operands, j, ncol, edge, d):
        p, ds = _p_ds(q, k, v, do, lse, delta, sm_scale, causal, 0, 0,
                      block_q, block_k)
        dvs.append(jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32))
        dks.append(jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32))
        dqs.append(jax.lax.dot_general(
            ds.astype(k.dtype), k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32))
    dq_ref[...] = _join(dqs).astype(dq_ref.dtype)
    dk_ref[...] = _join(dks).astype(dk_ref.dtype)
    dv_ref[...] = _join(dvs).astype(dv_ref.dtype)


def _packed_p_ds(q, k, v, do, lse, delta, half, sm_scale, causal, qi, ki,
                 block_q, block_k):
    """Shared packed-backward front half: recompute P and dS for a
    [G, bq, 2·bk] tile at K=128 contractions.  `lse`, `delta`: the two
    heads' columns.  Returns (p, ds, kbd)."""
    kbd = _block_diag_pack(k, half)                # [G, 2bk, 128]
    s = jax.lax.dot_general(
        q, kbd, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * (sm_scale * LOG2E)
    s = _mask_causal_packed(s, causal, qi, ki, block_q, block_k)
    p0 = jnp.exp2(s[:, :, :block_k] - lse[0])
    p1 = jnp.exp2(s[:, :, block_k:] - lse[1])
    p = jnp.concatenate([p0, p1], axis=-1)         # [G, bq, 2bk]
    vbd = _block_diag_pack(v, half)
    dp = jax.lax.dot_general(
        do, vbd, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)        # [G, bq, 2bk]
    ds0 = p0 * (dp[:, :, :block_k] - delta[0]) * sm_scale
    ds1 = p1 * (dp[:, :, block_k:] - delta[1]) * sm_scale
    ds = jnp.concatenate([ds0, ds1], axis=-1)
    return p, ds, kbd


def _packed_tile(operands, j, ncol, edge, **where):
    """The packed backward's tiles (`_bwd_tiles`) and what
    `_packed_p_ds` makes of them: (q, k, do, p, ds, kbd)."""
    half = operands[0].shape[-1] // 2
    q, k, v, do, lse, delta = _bwd_tiles(operands, j, ncol, edge, 2, half)
    p, ds, kbd = _packed_p_ds(q, k, v, do, lse, delta, half, **where)
    return q, k, do, p, ds, kbd


def _bwd_dkv_kernel_packed(*refs, sm_scale, causal, block_q, block_k,
                           edge, has_out, has_delta):
    operands, (dk_ref, dv_ref, dk_scr, dv_scr) = _bwd_refs(
        refs, has_out, has_delta)
    ki, j, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    ncol, nq = pl.num_programs(2), pl.num_programs(3)
    half = dk_ref.shape[-1] // 2

    @pl.when(qi == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        q, _, do, p, ds, _ = _packed_tile(
            operands, j, ncol, edge, sm_scale=sm_scale, causal=causal, qi=qi, ki=ki,
            block_q=block_q, block_k=block_k)
        # dV/dK come out row-stacked [G, 2bk, 128] with the useful
        # blocks on the block diagonal (K=bq, N=128 contractions)
        dv_stack = jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        dv_scr[...] += _block_diag_fold(dv_stack, half, block_k)
        dk_stack = jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        dk_scr[...] += _block_diag_fold(dk_stack, half, block_k)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel_packed(*refs, sm_scale, causal, block_q, block_k,
                          edge, has_out, has_delta):
    operands, (dq_ref, dq_scr) = _bwd_refs(refs, has_out, has_delta)
    qi, j, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    ncol, nk = pl.num_programs(2), pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        _, k, _, _, ds, kbd = _packed_tile(
            operands, j, ncol, edge, sm_scale=sm_scale, causal=causal, qi=qi, ki=ki,
            block_q=block_q, block_k=block_k)
        # dQ += dS Kbd: [G, bq, 2bk] x [G, 2bk, 128] (K=2bk, N=128); the
        # block-diagonal zeros route each half's keys to its own lanes
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), kbd, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_fused_kernel_packed(*refs, sm_scale, causal, block_q, block_k,
                             edge, has_out, has_delta):
    """Packed single-tile backward: one pass for dQ/dK/dV at K=128
    contractions (see `_bwd_fused_kernel`)."""
    operands, (dq_ref, dk_ref, dv_ref) = _bwd_refs(refs, has_out, has_delta)
    half = dq_ref.shape[-1] // 2
    q, k, do, p, ds, kbd = _packed_tile(
        operands, pl.program_id(1), pl.num_programs(1), edge, sm_scale=sm_scale,
        causal=causal, qi=0, ki=0, block_q=block_q, block_k=block_k)
    dv_stack = jax.lax.dot_general(
        p.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    dv_ref[...] = _block_diag_fold(dv_stack, half, block_k) \
        .astype(dv_ref.dtype)
    dk_stack = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    dk_ref[...] = _block_diag_fold(dk_stack, half, block_k) \
        .astype(dk_ref.dtype)
    dq_ref[...] = jax.lax.dot_general(
        ds.astype(k.dtype), kbd, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)


def _bwd(d, sm_scale, causal, block_q, block_k, interpret, pack, qkv, out,
         lse, g, delta=None):
    """Backward launcher over `qkv` as `_fwd` took it, `out` and dO `g`
    [B, T, C] and lse [B, T, H]; returns the cotangent of `qkv` in its
    own form (three [B, T, C], or the one [B, T, 3·C] joined).

    The kernels' δ is rowsum(dO ⊙ out), taken in the kernel from the
    tiles it holds anyway, plus `delta` [B, T, H] where given. That is
    where a cotangent of the (log2-space) LSE output enters:
    ∂lse/∂s_scaled = p·log2e, so the lse path contributes
    ds += p·log2e·dlse, algebraically a shift of δ by −log2e·dlse:
    ds = p·(dp − (δ − log2e·dlse))·scale. And the merged ring backward
    hands over its whole δ, derived from merge weights without ever
    materializing the per-step partial out (`out` is then None)."""
    operands = [*qkv, g, *(x for x in (out, lse, delta) if x is not None)]
    local = functools.partial(
        _bwd_local, n_qkv=len(qkv), d=d, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret, pack=pack,
        has_out=out is not None, has_delta=delta is not None)
    grads = per_device(
        local,
        in_dims=_qkv_dims(qkv) + (_BTC,) * (len(operands) - len(qkv)),
        out_dims=(_BTC,) * 3, cols=(_n_heads(qkv, d),))(*operands)
    if len(qkv) == 1:
        return (jnp.concatenate(grads, axis=-1),)
    return tuple(grads)


def _bwd_local(*operands, n_qkv, d, sm_scale, causal, block_q, block_k,
               interpret, pack, has_out, has_delta):
    """One device's backward launch: (dq, dk, dv), each [B, T, C]."""
    b, t, c, w, ncol, (q, k, v) = _columns(operands[:n_qkv], d)
    h = c // d
    score_k = (w // d) * block_k
    nq, nk = t // block_q, t // block_k
    static = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, edge=c % w, has_out=has_out,
                  has_delta=has_delta)
    if not pack:
        static["d"] = d
    suffix = "_packed" if pack else ""
    operands = (q[0], k[0], v[0]) + operands[n_qkv:]
    grad_shape = jax.ShapeDtypeStruct((b, t, c), q[0].dtype)
    # the unpacked kernels keep one accumulator a head
    lead = () if pack else (w // d,)
    acc_w = w if pack else d

    def in_specs(at_q, at_k, stat):
        """q, k, v, dO[, out], lse[, delta]"""
        return [at_q(q[1]), at_k(k[1]), at_k(v[1])] + \
            [at_q()] * (1 + has_out) + [stat] * (1 + has_delta)

    if nq == 1 and nk == 1:
        # whole sequence in one tile: fused one-pass backward (~4
        # score-sized fp32 tiles live: s, p, dp, ds). Bigger budgets
        # win on the isolated kernel but lose inside the full step —
        # see the forward's budget note.
        gf = _head_group(b, block_q, score_k, tile_budget=4 * 1024 * 1024)
        spec, _, stat = _specs(gf, t, t, w, h, None, None, 1)
        return tuple(pl.pallas_call(
            functools.partial(
                _bwd_fused_kernel_packed if pack else _bwd_fused_kernel,
                **static),
            name="flash_bwd_fused" + suffix,
            grid=(b // gf, ncol),
            compiler_params=_COMPILER_PARAMS,
            in_specs=in_specs(spec, spec, stat),
            out_specs=[spec()] * 3,
            out_shape=[grad_shape] * 3,
            interpret=interpret,
        )(*operands))

    gg = _head_group(b, block_q, score_k, tile_budget=2 * 1024 * 1024)

    # dKV sweep: grid (batch group, k block, column tile, q block)
    at_q, at_k, stat = _specs(gg, block_q, block_k, w, h, 3, 1, 2)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel_packed if pack else _bwd_dkv_kernel, **static),
        name="flash_bwd_dkv" + suffix,
        grid=(b // gg, nk, ncol, nq),
        compiler_params=_COMPILER_PARAMS,
        in_specs=in_specs(at_q, at_k, stat),
        out_specs=[at_k(), at_k()],
        out_shape=[grad_shape] * 2,
        scratch_shapes=[
            pltpu.VMEM(lead + (gg, block_k, acc_w), jnp.float32),
            pltpu.VMEM(lead + (gg, block_k, acc_w), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)

    # dQ sweep: grid (batch group, q block, column tile, k block)
    at_q, at_k, stat = _specs(gg, block_q, block_k, w, h, 1, 3, 2)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel_packed if pack else _bwd_dq_kernel, **static),
        name="flash_bwd_dq" + suffix,
        grid=(b // gg, nq, ncol, nk),
        compiler_params=_COMPILER_PARAMS,
        in_specs=in_specs(at_q, at_k, stat),
        out_specs=at_q(),
        out_shape=grad_shape,
        scratch_shapes=[pltpu.VMEM(lead + (gg, block_q, acc_w),
                                   jnp.float32)],
        interpret=interpret,
    )(*operands)
    return dq, dk, dv


# ----------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------
# Inside, `qkv` is a tuple (q, k, v [B, T, C], or the [B, T, 3·C]
# product alone), every other tensor [B, T, C] and every row statistic
# [B, T, H]; the public functions reshape (free) at their boundary.
_STATIC = tuple(range(1, 8))   # d, sm_scale, causal, blocks, interpret, pack


@functools.partial(jax.custom_vjp, nondiff_argnums=_STATIC)
def _flash(qkv, d, sm_scale, causal, block_q, block_k, interpret, pack):
    return _fwd(qkv, d, sm_scale, causal, block_q, block_k, interpret,
                pack)[0]


def _flash_fwd(qkv, *static):
    out, lse = _fwd(qkv, *static)
    return out, (qkv, out, lse)


def _flash_bwd(*args):
    *static, (qkv, out, lse), g = args
    return (_bwd(*static, qkv, out, lse, g),)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ----------------------------------------------------------------------
# (out, lse) form: differentiable partials for ring attention
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=_STATIC)
def _flash_lse(qkv, d, sm_scale, causal, block_q, block_k, interpret,
               pack):
    return _fwd(qkv, d, sm_scale, causal, block_q, block_k, interpret,
                pack)


def _flash_lse_fwd(qkv, *static):
    out, lse = _fwd(qkv, *static)
    return (out, lse), (qkv, out, lse)


def _flash_lse_bwd(*args):
    *static, (qkv, out, lse), (g_out, g_lse) = args
    return (_bwd(*static, qkv, out, lse, g_out, delta=-LOG2E * g_lse),)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _columns_of(*xs):
    """[B, T, H, D] -> [B, T, H·D], a free reshape."""
    return tuple(x.reshape(*x.shape[:2], -1) for x in xs)


def _stat_in(x):
    """A row statistic as the ring carries it, [B, H, T, 1], in the
    kernels' [B, T, H]."""
    return jnp.swapaxes(x[..., 0], 1, 2)


def _stat_out(x):
    return jnp.swapaxes(x, 1, 2)[..., None]


def flash_attention_with_lse(q, k, v, causal=True, sm_scale=None,
                             block_q=None, block_k=None,
                             interpret=None, head_packing="auto"):
    """Flash attention returning (out [B,T,H,D], lse [B,H,T,1]).

    The LSE is in LOG2 space (m + log2(l) over log2e-scaled scores, the
    kernel's native convention). Two partials over disjoint key sets
    merge exactly as m = max(lse1, lse2); w_i = exp2(lse_i − m);
    out = (out1·w1 + out2·w2)/(w1+w2); lse = m + log2(w1+w2) — the
    ring-attention per-step merge (ops/sequence/ring_attention.py,
    which fuses that merge into the kernel epilogue via
    `flash_attention_merge`). Fully differentiable: the lse cotangent
    enters the backward kernels as a shift of δ (see _bwd)."""
    args = _normalize_flash_args(q, k, v, causal, sm_scale, block_q,
                                 block_k, interpret, head_packing)
    out, lse = _flash_lse(_columns_of(q, k, v), q.shape[-1], *args)
    return out.reshape(q.shape), _stat_out(lse)


# ----------------------------------------------------------------------
# in-kernel merge with a prior partial: the ring-attention step body
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=tuple(range(3, 10)))
def _flash_merge(qkv, prev_out, prev_lse, d, sm_scale, causal, block_q,
                 block_k, interpret, pack):
    out, lse, _ = _fwd(qkv, d, sm_scale, causal, block_q, block_k,
                       interpret, pack, prev=(prev_out, prev_lse))
    return out, lse


def _flash_merge_fwd(qkv, prev_out, prev_lse, *static):
    out_m, lse_m, lse_n = _fwd(qkv, *static, prev=(prev_out, prev_lse))
    return (out_m, lse_m), (qkv, prev_out, prev_lse, out_m, lse_m, lse_n)


def _flash_merge_bwd(*args):
    """VJP of merge(flash(q,k,v), prev).  With a_p = w_p/W =
    2^(lse_p − lse_m) and a_n = w_n/W = 2^(lse_n − lse_m) (a_p+a_n = 1):

        d o_p   = ḡ_o · a_p            d o_n = ḡ_o · a_n
        d lse_p = ln2·a_p·(R_p − R_m) + ḡ_l·a_p
        d lse_n = ln2·a_p·(R_m − R_p) + ḡ_l·a_n
        δ_n     = Σ_d(d o_n ⊙ o_n) = R_m − a_p·R_p

    where R_x = Σ_d(ḡ_o ⊙ o_x).  Every quantity uses only the SAVED
    o_p/o_m/lses — the current partial o_n is never reconstructed (a
    naive o_n = (o_m·W − w_p·o_p)/w_n divides by a possibly-underflowed
    w_n).  δ_n, shifted by d lse_n, then drives the standard flash
    backward kernels directly (out=None).  Everything here is
    [B, T, C] beside [B, T, H]: a statistic meets a tensor through the
    free [B, T, H, D] view."""
    *static, res, (g_out, g_lse) = args
    qkv, prev_out, prev_lse, out_m, lse_m, lse_n = res
    d = static[0]
    b, t, c = g_out.shape

    def heads(x):                # [B, T, C] -> [B, T, H, D]
        return x.reshape(b, t, c // d, d)

    go = g_out.astype(jnp.float32)
    a_p = jnp.exp2(prev_lse.astype(jnp.float32) - lse_m)   # [B, T, H]
    a_n = jnp.exp2(lse_n - lse_m)

    def rowsum(x, y):            # [B, T, C] ⊙ [B, T, C] -> [B, T, H]
        return jnp.sum(heads(x * y.astype(jnp.float32)), axis=-1)

    r_m = rowsum(go, out_m)
    r_p = rowsum(go, prev_out)
    d_prev_out = (heads(go) * a_p[..., None]).reshape(b, t, c)
    d_o_n = (heads(g_out) * a_n[..., None].astype(g_out.dtype)) \
        .reshape(b, t, c)
    d_prev_lse = _LN2 * a_p * (r_p - r_m) + g_lse * a_p
    d_lse_n = _LN2 * a_p * (r_m - r_p) + g_lse * a_n
    delta_n = r_m - a_p * r_p

    d_qkv = _bwd(*static, qkv, None, lse_n, d_o_n,
                 delta=delta_n - LOG2E * d_lse_n)
    return d_qkv, d_prev_out, d_prev_lse


_flash_merge.defvjp(_flash_merge_fwd, _flash_merge_bwd)


def flash_attention_merge(q, k, v, prev_out, prev_lse, causal=True,
                          sm_scale=None, block_q=None,
                          block_k=None, interpret=None,
                          head_packing="auto"):
    """Flash attention over one KV block, merged IN THE KERNEL EPILOGUE
    with a prior softmax partial over a disjoint key set.

    prev_out [B,T,H,D] (any float dtype; promoted to fp32) and prev_lse
    [B,H,T,1] (log2 space, NEG_INF rows = empty partial) are the running
    ring-attention carry; returns the merged (out fp32 [B,T,H,D],
    lse [B,H,T,1]).  Equivalent to `flash_attention_with_lse` followed
    by the two-partial merge formula, but the per-step partial never
    round-trips HBM through an XLA elementwise chain — the kernel folds
    the previous carry into its epilogue write
    (`ops/sequence/ring_attention.py` is the caller).  Differentiable
    in q, k, v, prev_out and prev_lse."""
    args = _normalize_flash_args(q, k, v, causal, sm_scale, block_q,
                                 block_k, interpret, head_packing)
    out, lse = _flash_merge(
        _columns_of(q, k, v),
        *_columns_of(prev_out.astype(jnp.float32)), _stat_in(prev_lse),
        q.shape[-1], *args)
    return out.reshape(q.shape), _stat_out(lse)


# ----------------------------------------------------------------------
# remat-friendly form: never re-run the forward kernel in backward
# ----------------------------------------------------------------------
# Under `jax.checkpoint`, a custom_vjp op is atomic: the backward pass
# re-runs its FORWARD to regenerate residuals, so rematted transformer
# blocks pay the (expensive) flash forward kernel twice.
# The split below routes the residuals AROUND the remat boundary:
#
#     out, lse = _fwd(qkv)                   # fwd kernel, NOT differentiable
#     out = checkpoint_name(out, "attn_out") # 2 B/elem per layer
#     lse = checkpoint_name(lse, "attn_lse") # 4 B/token per layer
#     out = _flash_apply(qkv, out, lse)      # identity fwd; custom bwd
#
# With a `save_only_these_names:attn_out,attn_lse` policy the named
# values are saved, the forward launch is dead in the recompute (its
# only outputs are saved) and never re-runs, while `_flash_apply`'s VJP
# runs the dq/dkv kernels directly from the saved residuals — q, k, v
# are recomputed by the (cheap) qkv-matmul chain remat. Without such a
# policy the behavior degrades gracefully to plain full remat.
@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 10)))
def _flash_apply(qkv, out, lse, d, sm_scale, causal, block_q, block_k,
                 interpret, pack):
    return out


def _flash_apply_fwd(qkv, out, lse, *static):
    return out, (qkv, out, lse)


def _flash_apply_bwd(*args):
    *static, (qkv, out, lse), g = args
    # out/lse enter via the non-differentiable forward kernel (gradient
    # flows exclusively through q, k, v — mathematically out = f(q,k,v))
    return (_bwd(*static, qkv, out, lse, g), jnp.zeros_like(out),
            jnp.zeros_like(lse))


_flash_apply.defvjp(_flash_apply_fwd, _flash_apply_bwd)


def _normalize_flash_args(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret, head_packing="auto"):
    """Shared argument validation/defaulting for all flash entry
    points — they must never diverge (the rematerializable form
    guarantees identical numerics).  q, k, v: anything with the
    [B, T, H, D] shape and a dtype."""
    assert q.shape == k.shape == v.shape, (q.shape, k.shape, v.shape)
    t = q.shape[1]
    if block_q is None and block_k is None:
        # caller did not pick tiles (None is the sentinel — an
        # EXPLICIT 1024/1024 stays 1024/1024): consult the autotune
        # table (a pure host-side dict lookup at trace time; returns
        # only divisors of t, validated on load), else the
        # hand-picked default.
        from deepspeed_tpu.ops import autotune
        if interpret is None:
            _interp_probe = not _on_tpu()
        else:
            _interp_probe = bool(interpret)
        _pack_probe = _resolve_head_packing(head_packing, q.shape[-1],
                                            _interp_probe)
        tuned = autotune.flash_blocks(t, q.shape[-1], bool(causal),
                                      _pack_probe, q.dtype)
        if tuned is not None:
            block_q, block_k = tuned
    block_q = _DEFAULT_BLOCK if block_q is None else block_q
    block_k = _DEFAULT_BLOCK if block_k is None else block_k
    block_q = _fit_block(block_q, t)
    block_k = _fit_block(block_k, t)
    assert t % block_q == 0 and t % block_k == 0, (
        f"seq_len {t} must divide by block sizes ({block_q}, {block_k}); "
        "pad the sequence or pass smaller block_q/block_k")
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    if interpret is None:
        interpret = not _on_tpu()
    pack = _resolve_head_packing(head_packing, q.shape[-1],
                                 bool(interpret))
    return (float(sm_scale), bool(causal), int(block_q), int(block_k),
            bool(interpret), pack)


def _attend(qkv, static, rematerializable):
    """`qkv` (the tuple the launchers take) -> out [B, T, C]."""
    if not rematerializable:
        return _flash(qkv, *static)
    from jax.ad_checkpoint import checkpoint_name
    out, lse = _fwd(tuple(jax.lax.stop_gradient(x) for x in qkv), *static)
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return _flash_apply(qkv, out, lse, *static)


def flash_attention_rematerializable(q, k, v, causal=True, sm_scale=None,
                                     block_q=None,
                                     block_k=None,
                                     interpret=None, head_packing="auto"):
    """flash_attention whose (out, lse) carry checkpoint_name
    annotations ("attn_out"/"attn_lse") so a names-saving remat policy
    skips the forward-kernel re-run in backward. Numerics identical to
    `flash_attention`."""
    args = _normalize_flash_args(q, k, v, causal, sm_scale, block_q,
                                 block_k, interpret, head_packing)
    return _attend(_columns_of(q, k, v), (q.shape[-1],) + args,
                   True).reshape(q.shape)


def flash_attention(q, k, v, causal=True, sm_scale=None,
                    block_q=None, block_k=None,
                    interpret=None, head_packing="auto"):
    """Flash attention over [B, T, H, D] tensors; returns [B, T, H, D].

    interpret=None auto-selects Pallas interpreter mode off-TPU so the
    same kernel code is exercised by CPU tests.  head_packing
    ("auto"|"packed"|"off") selects the K=128 contraction over a d=64
    column tile's two heads (auto: on real TPU only; packed/off force it
    on/off; see module docstring).
    """
    args = _normalize_flash_args(q, k, v, causal, sm_scale, block_q,
                                 block_k, interpret, head_packing)
    return _attend(_columns_of(q, k, v), (q.shape[-1],) + args,
                   False).reshape(q.shape)


def _heads_of_product(qkv, n_head):
    """What q, k and v each are inside the product [B, T, 3·H·D]: the
    [B, T, H, D] shape and the dtype."""
    b, t, c = qkv.shape[0], qkv.shape[1], qkv.shape[2] // 3
    return jax.ShapeDtypeStruct((b, t, n_head, c // n_head), qkv.dtype)


def flash_attention_qkv_usable(qkv, n_head, no_dropout: bool):
    """Whether `flash_attention_qkv` can read q, k and v out of the
    `c_attn` product where it lies: the kernel's own conditions, H·D a
    whole number of column tiles (k and v then start on a tile), and no
    tensor parallelism over the product's columns (a shard of
    [q | k | v] is not a [q | k | v])."""
    if qkv.ndim != 3 or qkv.shape[-1] % (3 * n_head):
        return False
    q = _heads_of_product(qkv, n_head)
    return flash_attention_usable(q, no_dropout) and \
        (n_head * q.shape[-1]) % _tile_width(q.shape[-1]) == 0 and \
        not divides_cols(qkv)


def flash_attention_qkv(qkv, n_head, causal=True, sm_scale=None,
                        block_q=None, block_k=None, interpret=None,
                        head_packing="auto", rematerializable=False):
    """`flash_attention` (or, with `rematerializable`, its
    rematerializable form) over the projection's product
    [B, T, 3·H·D] = [q | k | v] whole; returns [B, T, H, D]. Bit-for-bit
    what the split product gives: the kernels read the same tiles
    through three index maps on one operand, and no slice of it is
    copied, forward or backward (`flash_attention_qkv_usable` says
    where)."""
    q = _heads_of_product(qkv, n_head)
    if not flash_attention_qkv_usable(qkv, n_head, True):
        raise ValueError(
            f"flash_attention_qkv cannot read q, k and v out of a "
            f"{qkv.shape} product of {n_head} heads where it lies (see "
            "flash_attention_qkv_usable): split it and call "
            "flash_attention")
    args = _normalize_flash_args(q, q, q, causal, sm_scale, block_q,
                                 block_k, interpret, head_packing)
    return _attend((qkv,), (q.shape[-1],) + args,
                   rematerializable).reshape(q.shape)
