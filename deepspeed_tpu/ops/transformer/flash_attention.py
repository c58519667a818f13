"""Flash attention as a Pallas TPU kernel.

TPU-native replacement for the reference's fused CUDA attention chain
(`csrc/transformer/softmax_kernels.cu`, `strided_batch_gemm.h`,
`ds_transformer_cuda.cpp:1026-1044`): instead of materializing the
[B, H, T, T] score tensor in HBM, the kernel streams K/V blocks through
VMEM with an online-softmax running (m, l) pair, so HBM traffic is
O(T·d) and the MXU sees back-to-back [block_q, d]×[d, block_k] matmuls.

Layout: [B, T, H, D] in/out (the model's native layout); the kernel grid
is (B·H, T/block_q, T/block_k) with K innermost so the (m, l, acc)
scratch carries across K blocks.  Backward is the standard two-kernel
flash backward (dKV sweep + dQ sweep) off saved logsumexp rows — the
reference instead checkpoints 17 intermediate activations
(`ops/transformer/transformer.py:155-213`).

Head packing (d = 64).  The MXU contracts 128 elements per pass, so a
d=64 attention runs its QK^T at K=64 (half the systolic rows idle) and
its PV at N=64 (half the lanes idle; what that costs is not measured
on the current installation).  With `head_packing` the kernel
processes TWO heads per grid step in a feature-packed layout
[rows, T, 128] (adjacent B·H rows pair up; an odd B·H count pads one
zero row that is sliced off):

    Qp  = [q0 | q1]                          [bq, 128]   (dense)
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
interpreter path, d ≠ 64, and `"off"` use the unpacked kernel.

Ring-attention partial merge.  `flash_attention_merge` fuses the ring
step's (out, lse) softmax-partial merge into the kernel epilogue: the
previous partial rides in as two extra refs and the merged result is
written directly, so the per-step partial never round-trips HBM through
an XLA elementwise merge chain (`ops/sequence/ring_attention.py`).

On non-TPU backends the same kernels run in Pallas interpreter mode so
CPU CI validates kernel logic bit-for-bit against the XLA reference path.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.per_device import COLS, ROWS, per_device

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
    and D a lane-friendly multiple of 64; dropout stays on the XLA path."""
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
    return t % block_q == 0 and t % block_k == 0 and d % 64 == 0 and \
        t >= 128 and t % 128 == 0


def _resolve_head_packing(head_packing, d, interpret):
    """Head-packing mode -> bool.  "auto" packs d=64 heads pairwise on
    real TPU (K=128 contractions); the interpreter path stays unpacked
    so CPU CI timings/VMEM budgets reflect the per-head kernel unless a
    test forces "packed".  Odd B·H counts are handled by a one-row zero
    pad, NOT a fallback — the flagship's 11×25 = 275 rows still pack."""
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
# packed-layout helpers
# ----------------------------------------------------------------------
def _pack_pairs(x):
    """[rows, T, d] -> [ceil(rows/2), T, 2·d]: adjacent rows pair up
    feature-wise (row 2i in lanes [:d], row 2i+1 in lanes [d:]); an odd
    row count pads one zero row.  Also packs [rows, T, 1] lse/delta
    columns into [pairs, T, 2]."""
    rows, t, d = x.shape
    if rows % 2:
        x = jnp.concatenate([x, jnp.zeros((1, t, d), x.dtype)], axis=0)
    pairs = (rows + 1) // 2
    return x.reshape(pairs, 2, t, d).transpose(0, 2, 1, 3) \
        .reshape(pairs, t, 2 * d)


def _unpack_pairs(x, rows):
    """Inverse of `_pack_pairs`, slicing off the odd-count pad row."""
    pairs, t, dd = x.shape
    d = dd // 2
    x = x.reshape(pairs, t, 2, d).transpose(0, 2, 1, 3) \
        .reshape(2 * pairs, t, d)
    return x[:rows]


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
    """Collapse a half-broadcast [G, bq, 2·half] stat to its two
    representative columns [G, bq, 2]."""
    return jnp.concatenate([x[:, :, :1], x[:, :, half:half + 1]], axis=-1)


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
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal,
                block_q, block_k, merge):
    if merge:
        (po_ref, plse_ref, o_ref, lse_ref, lse_n_ref,
         m_scr, l_scr, acc_scr) = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: a K block strictly above the diagonal contributes nothing —
    # skip its matmuls entirely (the grid still visits it).
    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        q = q_ref[...]                            # [G, bq, d] native dtype
        k = k_ref[...]                            # [G, bk, d]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * (sm_scale * LOG2E)
        s = _mask_causal(s, causal, qi, ki, block_q, block_k)

        m_prev = m_scr[:, :, :1]                   # [G, bq, 1]
        l_prev = l_scr[:, :, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp2(s - m_new)                    # [G, bq, bk]
        alpha = jnp.exp2(m_prev - m_new)           # [G, bq, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

        v = v_ref[...]                             # [G, bk, d]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)    # [G, bq, d]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[:, :, :1] = m_new
        l_scr[:, :, :1] = l_new

    @pl.when(ki == nk - 1)
    def _():
        m = m_scr[:, :, :1]
        l = l_scr[:, :, :1]
        # log2-space LSE (= natural lse · log2e); consumed only by the
        # backward kernels, which stay in the same space
        lse_n = m + jnp.log2(l)
        if merge:
            # in-kernel softmax-partial merge: fold the previous ring
            # partial into this pass's (m, l, acc) before the single
            # HBM write (ops/sequence/ring_attention.py)
            plse = plse_ref[...]                   # [G, bq, 1]
            mm = jnp.maximum(lse_n, plse)
            w_p = jnp.exp2(plse - mm)
            # w_n/ l == exp2(m - mm): acc is unnormalized, so its merge
            # weight folds the 1/l normalization in
            wsum = w_p + jnp.exp2(lse_n - mm)
            out = (po_ref[...] * w_p +
                   acc_scr[...] * jnp.exp2(m - mm)) / wsum
            o_ref[...] = out.astype(o_ref.dtype)
            lse_ref[...] = mm + jnp.log2(wsum)
            lse_n_ref[...] = lse_n
        else:
            o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
            lse_ref[...] = lse_n


def _fwd_kernel_packed(q_ref, k_ref, v_ref, *rest, sm_scale, causal,
                       block_q, block_k, merge):
    """Two heads per grid step in the feature-packed layout: the QK^T
    contraction runs at K=128 and PV at N=128 (see module docstring).
    m/l scratch is half-broadcast-stored ([G, bq, 128] with each head's
    stat replicated across its 64 lanes) so alpha/l apply to the packed
    acc with plain elementwise ops."""
    if merge:
        (po_ref, plse_ref, o_ref, lse_ref, lse_n_ref,
         m_scr, l_scr, acc_scr) = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    half = q_ref.shape[-1] // 2

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        q = q_ref[...]                             # [G, bq, 128]
        k = k_ref[...]                             # [G, bk, 128]
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

        v = v_ref[...]
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
            plse = plse_ref[...]                   # [G, bq, 2]
            plse_b = _halves(plse[:, :, :1], plse[:, :, 1:2], half)
            mm = jnp.maximum(lse_n, plse_b)
            w_p = jnp.exp2(plse_b - mm)
            wsum = w_p + jnp.exp2(lse_n - mm)
            out = (po_ref[...] * w_p +
                   acc_scr[...] * jnp.exp2(m - mm)) / wsum
            o_ref[...] = out.astype(o_ref.dtype)
            lse_ref[...] = _two_cols(mm + jnp.log2(wsum), half)
            lse_n_ref[...] = _two_cols(lse_n, half)
        else:
            o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
            lse_ref[...] = _two_cols(lse_n, half)


def _head_group(bh, block_q, block_k, d, tile_budget=8 * 1024 * 1024):
    """Largest head-group G (≤ default) dividing B·H, with the fp32 score
    tile capped to `tile_budget` bytes of VMEM (the backward kernels keep
    ~4 score-sized tiles live, so they pass a smaller budget)."""
    g = _DEFAULT_HEAD_GROUP
    cap = max(1, tile_budget // (block_q * block_k * 4))
    g = min(g, cap)
    while bh % g:
        g -= 1
    return max(g, 1)


# operand layouts, as per_device dims: the batch divides with the rows
# of the mesh, the heads with its columns
_BTHD = (ROWS, None, COLS, None)
_BHTX = (ROWS, COLS, None, None)


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, pack,
         prev=None):
    """Forward launcher.  Returns (out [bh, t, d], lse [bh, t, 1]); with
    `prev = (prev_out [B,T,H,D], prev_lse [B,H,T,1])` the kernel merges
    the prior softmax partial in its epilogue and additionally returns
    the CURRENT partial's lse_n [bh, t, 1] (the backward residual)."""
    b, t, h, d = q.shape
    merge = prev is not None
    local = functools.partial(
        _fwd_local, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, pack=pack)
    outs = per_device(
        local,
        in_dims=(_BTHD,) * 3 + ((_BTHD, _BHTX) if merge else ()),
        out_dims=(_BHTX,) * (3 if merge else 2))(
            q, k, v, *(prev if merge else ()))
    return tuple(o.reshape(b * h, t, o.shape[-1]) for o in outs)


def _fwd_local(q, k, v, *prev, sm_scale, causal, block_q, block_k,
               interpret, pack):
    """One device's launch: [B, T, H, D] blocks in, (out [B, H, T, D],
    lse [B, H, T, 1][, lse_n]) out."""
    b, t, h, d = q.shape
    bh = b * h
    merge = bool(prev)

    # [B, T, H, D] -> [B*H, T, D]
    def to_bht(x):
        return x.transpose(0, 2, 1, 3).reshape(bh, t, d)
    qt, kt, vt = to_bht(q), to_bht(k), to_bht(v)
    if merge:
        prev_out, prev_lse = prev
        pot = to_bht(prev_out.astype(jnp.float32))
        plse = prev_lse.astype(jnp.float32).reshape(bh, t, 1)

    if pack:
        qt, kt, vt = _pack_pairs(qt), _pack_pairs(kt), _pack_pairs(vt)
        if merge:
            pot, plse = _pack_pairs(pot), _pack_pairs(plse)
    rows = qt.shape[0]                    # bh, or padded pair count
    dl = qt.shape[-1]                     # d, or 2·d packed
    lanes = 2 if pack else 1              # lse columns per row

    # 8 MB score-tile budget. A 24 MB budget (g=5 at the flagship
    # shape) measures ~20% faster on the ISOLATED kernel chain but ~1%
    # slower inside the full train step (VMEM pressure against the
    # surrounding fusions) — keep the in-model winner.  The packed tile
    # is [bq, 2·bk], so the same budget halves G there.
    g = _head_group(rows, block_q, (2 if pack else 1) * block_k, dl)
    nq, nk = t // block_q, t // block_k
    grid = (rows // g, nq, nk)
    kernel_fn = _fwd_kernel_packed if pack else _fwd_kernel
    kernel = functools.partial(kernel_fn, sm_scale=sm_scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, merge=merge)

    def q_spec(width):
        return pl.BlockSpec((g, block_q, width),
                            lambda bhi, qi, ki: (bhi, qi, 0))

    kv_spec = pl.BlockSpec((g, block_k, dl),
                           lambda bhi, qi, ki: (bhi, ki, 0))
    in_specs = [q_spec(dl), kv_spec, kv_spec]
    operands = [qt, kt, vt]
    out_specs = [q_spec(dl), q_spec(lanes)]
    out_shape = [
        jax.ShapeDtypeStruct((rows, t, dl),
                             jnp.float32 if merge else q.dtype),
        jax.ShapeDtypeStruct((rows, t, lanes), jnp.float32),
    ]
    if merge:
        in_specs += [q_spec(dl), q_spec(lanes)]
        operands += [pot, plse]
        out_specs.append(q_spec(lanes))
        out_shape.append(
            jax.ShapeDtypeStruct((rows, t, lanes), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        name="flash_fwd" + ("_packed" if pack else ""),
        grid=grid,
        compiler_params=_COMPILER_PARAMS,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((g, block_q, max(dl, 128)), jnp.float32),
            pltpu.VMEM((g, block_q, max(dl, 128)), jnp.float32),
            pltpu.VMEM((g, block_q, dl), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    if pack:
        outs = [_unpack_pairs(o, bh) for o in outs]
    return tuple(o.reshape(b, h, t, o.shape[-1]) for o in outs)


# ----------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    block_q, block_k):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        q = q_ref[...]                             # [G, bq, d] native dtype
        k = k_ref[...]                             # [G, bk, d]
        v = v_ref[...]
        do = do_ref[...]                           # [G, bq, d]
        lse = lse_ref[...]                         # [G, bq, 1]
        delta = delta_ref[...]                     # [G, bq, 1]

        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * (sm_scale * LOG2E)
        s = _mask_causal(s, causal, qi, ki, block_q, block_k)
        p = jnp.exp2(s - lse)                      # [G, bq, bk]

        # dV += Pᵀ dO
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        # dP = dO Vᵀ ; dS = P ⊙ (dP − δ) · scale
        dp = jax.lax.dot_general(
            do, v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        # dK += dSᵀ Q
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, sm_scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        q = q_ref[...]                             # [G, bq, d]
        k = k_ref[...]                             # [G, bk, d]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...]
        delta = delta_ref[...]

        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * (sm_scale * LOG2E)
        s = _mask_causal(s, causal, qi, ki, block_q, block_k)
        p = jnp.exp2(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        # dQ += dS K
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, sm_scale, causal,
                      block_q, block_k):
    """Single-tile backward (T == block): s, p and dP exist once, so
    dQ, dK and dV all come out of ONE pass — the two-kernel flash
    backward recomputes s/p (and dP) in each sweep, paying ~2x the
    matmul+exp work at tiles the VMEM can hold whole."""
    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    do = do_ref[...]
    lse = lse_ref[...]
    delta = delta_ref[...]

    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * (sm_scale * LOG2E)
    s = _mask_causal(s, causal, 0, 0, block_q, block_k)
    p = jnp.exp2(s - lse)
    dv_ref[...] = jax.lax.dot_general(
        p.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(
        do, v, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * sm_scale
    dk_ref[...] = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)
    dq_ref[...] = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)


def _packed_p_ds(q, k, v, do, lse, delta, half, sm_scale, causal, qi, ki,
                 block_q, block_k):
    """Shared packed-backward front half: recompute P and dS for a
    [G, bq, 2·bk] tile at K=128 contractions.  Returns (p, ds, kbd)."""
    kbd = _block_diag_pack(k, half)                # [G, 2bk, 128]
    s = jax.lax.dot_general(
        q, kbd, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * (sm_scale * LOG2E)
    s = _mask_causal_packed(s, causal, qi, ki, block_q, block_k)
    p0 = jnp.exp2(s[:, :, :block_k] - lse[:, :, :1])
    p1 = jnp.exp2(s[:, :, block_k:] - lse[:, :, 1:2])
    p = jnp.concatenate([p0, p1], axis=-1)         # [G, bq, 2bk]
    vbd = _block_diag_pack(v, half)
    dp = jax.lax.dot_general(
        do, vbd, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)        # [G, bq, 2bk]
    ds0 = p0 * (dp[:, :, :block_k] - delta[:, :, :1]) * sm_scale
    ds1 = p1 * (dp[:, :, block_k:] - delta[:, :, 1:2]) * sm_scale
    ds = jnp.concatenate([ds0, ds1], axis=-1)
    return p, ds, kbd


def _bwd_dkv_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale,
                           causal, block_q, block_k):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    half = q_ref.shape[-1] // 2

    @pl.when(qi == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        q = q_ref[...]
        do = do_ref[...]
        p, ds, _ = _packed_p_ds(q, k_ref[...], v_ref[...], do,
                                lse_ref[...], delta_ref[...], half,
                                sm_scale, causal, qi, ki, block_q,
                                block_k)
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


def _bwd_dq_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dq_scr, *, sm_scale, causal, block_q,
                          block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    half = q_ref.shape[-1] // 2

    @pl.when(ki == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    visible = True
    if causal:
        visible = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(visible)
    def _():
        k = k_ref[...]
        _, ds, kbd = _packed_p_ds(q_ref[...], k, v_ref[...], do_ref[...],
                                  lse_ref[...], delta_ref[...], half,
                                  sm_scale, causal, qi, ki, block_q,
                                  block_k)
        # dQ += dS Kbd: [G, bq, 2bk] x [G, 2bk, 128] (K=2bk, N=128); the
        # block-diagonal zeros route each half's keys to its own lanes
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), kbd, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_fused_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref,
                             delta_ref, dq_ref, dk_ref, dv_ref, *,
                             sm_scale, causal, block_q, block_k):
    """Packed single-tile backward: one pass for dQ/dK/dV at K=128
    contractions (see `_bwd_fused_kernel`)."""
    half = q_ref.shape[-1] // 2
    q = q_ref[...]
    k = k_ref[...]
    do = do_ref[...]
    p, ds, kbd = _packed_p_ds(q, k, v_ref[...], do, lse_ref[...],
                              delta_ref[...], half, sm_scale, causal,
                              0, 0, block_q, block_k)
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


def _bwd(sm_scale, causal, block_q, block_k, interpret, res, g,
         dlse=None, pack=False, delta=None):
    """dlse: optional [bh, t, 1] cotangent of the (log2-space) LSE
    output. ∂lse/∂s_scaled = p·log2e, so the lse path contributes
    ds += p·log2e·dlse — algebraically a shift of δ:
    ds = p·(dp − (δ − log2e·dlse))·scale. The kernels stay unchanged;
    only the δ row vector moves.

    delta: optional precomputed δ = rowsum(dO ⊙ O) [bh, t, 1] — the
    merged ring backward derives it from merge weights without ever
    materializing the per-step partial out (res[3] may then be None)."""
    q, k, v, out, lse = res
    b, t, h, d = q.shape
    if delta is None:
        # δ = rowsum(dO ⊙ O) — computed by XLA (one fused
        # elementwise+reduce)
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True) \
            .transpose(0, 2, 1, 3)                  # [b, h, t, 1]
    delta = delta.reshape(b, h, t, 1)
    if dlse is not None:
        delta = delta - LOG2E * dlse.astype(jnp.float32) \
            .reshape(b, h, t, 1)
    local = functools.partial(
        _bwd_local, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, pack=pack)
    return per_device(
        local, in_dims=(_BTHD,) * 4 + (_BHTX,) * 2,
        out_dims=(_BTHD,) * 3)(
            q, k, v, g, lse.reshape(b, h, t, 1), delta)


def _bwd_local(q, k, v, g, lse, delta, *, sm_scale, causal, block_q,
               block_k, interpret, pack):
    """One device's backward launch: [B, T, H, D] blocks of q, k, v, dO
    and [B, H, T, 1] row statistics in, (dq, dk, dv) [B, T, H, D] out."""
    b, t, h, d = q.shape
    bh = b * h

    def to_bht(x):
        return x.transpose(0, 2, 1, 3).reshape(bh, t, d)

    def from_bht(x):
        return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    qt, kt, vt, dot_ = to_bht(q), to_bht(k), to_bht(v), to_bht(g)
    lse = lse.reshape(bh, t, 1)
    delta = delta.reshape(bh, t, 1)

    if pack:
        qt, kt, vt, dot_ = map(_pack_pairs, (qt, kt, vt, dot_))
        lse_in = _pack_pairs(lse)
        delta_in = _pack_pairs(delta)
    else:
        lse_in, delta_in = lse, delta
    rows = qt.shape[0]
    dl = qt.shape[-1]
    lanes = 2 if pack else 1
    score_k = (2 if pack else 1) * block_k

    def unpack(x):
        return from_bht(_unpack_pairs(x, bh) if pack else x)

    nq, nk = t // block_q, t // block_k

    if nq == 1 and nk == 1:
        # whole sequence in one tile: fused one-pass backward (~4
        # score-sized fp32 tiles live: s, p, dp, ds). Bigger budgets
        # win on the isolated kernel but lose inside the full step —
        # see the forward's budget note.
        gf = _head_group(rows, block_q, score_k, dl,
                         tile_budget=4 * 1024 * 1024)
        fused = functools.partial(
            _bwd_fused_kernel_packed if pack else _bwd_fused_kernel,
            sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k)
        specs = pl.BlockSpec((gf, t, dl), lambda i: (i, 0, 0))
        row_spec = pl.BlockSpec((gf, t, lanes), lambda i: (i, 0, 0))
        dq, dk, dv = pl.pallas_call(
            fused,
            name="flash_bwd_fused" + ("_packed" if pack else ""),
            grid=(rows // gf,),
            compiler_params=_COMPILER_PARAMS,
            in_specs=[specs, specs, specs, specs, row_spec, row_spec],
            out_specs=[specs, specs, specs],
            out_shape=[jax.ShapeDtypeStruct((rows, t, dl), q.dtype),
                       jax.ShapeDtypeStruct((rows, t, dl), k.dtype),
                       jax.ShapeDtypeStruct((rows, t, dl), v.dtype)],
            interpret=interpret,
        )(qt, kt, vt, dot_, lse_in, delta_in)
        return unpack(dq), unpack(dk), unpack(dv)

    gg = _head_group(rows, block_q, score_k, dl,
                     tile_budget=2 * 1024 * 1024)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel_packed if pack else _bwd_dkv_kernel,
        sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv" + ("_packed" if pack else ""),
        grid=(rows // gg, nk, nq),
        compiler_params=_COMPILER_PARAMS,
        in_specs=[
            pl.BlockSpec((gg, block_q, dl),
                         lambda bhi, ki, qi: (bhi, qi, 0)),
            pl.BlockSpec((gg, block_k, dl),
                         lambda bhi, ki, qi: (bhi, ki, 0)),
            pl.BlockSpec((gg, block_k, dl),
                         lambda bhi, ki, qi: (bhi, ki, 0)),
            pl.BlockSpec((gg, block_q, dl),
                         lambda bhi, ki, qi: (bhi, qi, 0)),
            pl.BlockSpec((gg, block_q, lanes),
                         lambda bhi, ki, qi: (bhi, qi, 0)),
            pl.BlockSpec((gg, block_q, lanes),
                         lambda bhi, ki, qi: (bhi, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((gg, block_k, dl),
                         lambda bhi, ki, qi: (bhi, ki, 0)),
            pl.BlockSpec((gg, block_k, dl),
                         lambda bhi, ki, qi: (bhi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, t, dl), k.dtype),
            jax.ShapeDtypeStruct((rows, t, dl), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((gg, block_k, dl), jnp.float32),
            pltpu.VMEM((gg, block_k, dl), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, dot_, lse_in, delta_in)

    dq_kernel = functools.partial(
        _bwd_dq_kernel_packed if pack else _bwd_dq_kernel,
        sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq" + ("_packed" if pack else ""),
        grid=(rows // gg, nq, nk),
        compiler_params=_COMPILER_PARAMS,
        in_specs=[
            pl.BlockSpec((gg, block_q, dl),
                         lambda bhi, qi, ki: (bhi, qi, 0)),
            pl.BlockSpec((gg, block_k, dl),
                         lambda bhi, qi, ki: (bhi, ki, 0)),
            pl.BlockSpec((gg, block_k, dl),
                         lambda bhi, qi, ki: (bhi, ki, 0)),
            pl.BlockSpec((gg, block_q, dl),
                         lambda bhi, qi, ki: (bhi, qi, 0)),
            pl.BlockSpec((gg, block_q, lanes),
                         lambda bhi, qi, ki: (bhi, qi, 0)),
            pl.BlockSpec((gg, block_q, lanes),
                         lambda bhi, qi, ki: (bhi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((gg, block_q, dl),
                               lambda bhi, qi, ki: (bhi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, t, dl), q.dtype),
        scratch_shapes=[pltpu.VMEM((gg, block_q, dl), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot_, lse_in, delta_in)

    return unpack(dq), unpack(dk), unpack(dv)


# ----------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret, pack):
    out, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                  pack)
    b, t, h, d = q.shape
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               pack):
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k,
                    interpret, pack)
    b, t, h, d = q.shape
    out_bthd = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return out_bthd, (q, k, v, out_bthd, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, pack, res,
               g):
    return _bwd(sm_scale, causal, block_q, block_k, interpret, res, g,
                pack=pack)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ----------------------------------------------------------------------
# (out, lse) form: differentiable partials for ring attention
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               pack):
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k,
                    interpret, pack)
    b, t, h, d = q.shape
    return (out.reshape(b, h, t, d).transpose(0, 2, 1, 3),
            lse.reshape(b, h, t, 1))


def _flash_lse_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                   pack):
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k,
                    interpret, pack)
    b, t, h, d = q.shape
    out_bthd = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return (out_bthd, lse.reshape(b, h, t, 1)), (q, k, v, out_bthd, lse)


def _flash_lse_bwd(sm_scale, causal, block_q, block_k, interpret, pack,
                   res, g):
    g_out, g_lse = g
    b = res[0].shape[0]
    h = res[0].shape[2]
    t = res[0].shape[1]
    return _bwd(sm_scale, causal, block_q, block_k, interpret, res, g_out,
                dlse=g_lse.reshape(b * h, t, 1), pack=pack)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


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
    enters the backward kernels as a δ shift (see _bwd)."""
    args = _normalize_flash_args(q, k, v, causal, sm_scale, block_q,
                                 block_k, interpret, head_packing)
    return _flash_lse(q, k, v, *args)


# ----------------------------------------------------------------------
# in-kernel merge with a prior partial: the ring-attention step body
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_merge(q, k, v, prev_out, prev_lse, sm_scale, causal, block_q,
                 block_k, interpret, pack):
    out, lse, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k,
                       interpret, pack, prev=(prev_out, prev_lse))
    b, t, h, d = q.shape
    return (out.reshape(b, h, t, d).transpose(0, 2, 1, 3),
            lse.reshape(b, h, t, 1))


def _flash_merge_fwd(q, k, v, prev_out, prev_lse, sm_scale, causal,
                     block_q, block_k, interpret, pack):
    out, lse, lse_n = _fwd(q, k, v, sm_scale, causal, block_q, block_k,
                           interpret, pack, prev=(prev_out, prev_lse))
    b, t, h, d = q.shape
    out_bthd = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    lse_m = lse.reshape(b, h, t, 1)
    return (out_bthd, lse_m), (q, k, v, prev_out, prev_lse, out_bthd,
                               lse_m, lse_n)


def _flash_merge_bwd(sm_scale, causal, block_q, block_k, interpret, pack,
                     res, g):
    """VJP of merge(flash(q,k,v), prev).  With a_p = w_p/W =
    2^(lse_p − lse_m) and a_n = w_n/W = 2^(lse_n − lse_m) (a_p+a_n = 1):

        d o_p   = ḡ_o · a_p            d o_n = ḡ_o · a_n
        d lse_p = ln2·a_p·(R_p − R_m) + ḡ_l·a_p
        d lse_n = ln2·a_p·(R_m − R_p) + ḡ_l·a_n
        δ_n     = Σ_d(d o_n ⊙ o_n) = R_m − a_p·R_p

    where R_x = Σ_d(ḡ_o ⊙ o_x).  Every quantity uses only the SAVED
    o_p/o_m/lses — the current partial o_n is never reconstructed (a
    naive o_n = (o_m·W − w_p·o_p)/w_n divides by a possibly-underflowed
    w_n).  δ_n and d lse_n then drive the standard flash backward
    kernels directly (res out=None, delta= precomputed)."""
    q, k, v, prev_out, prev_lse, out_m, lse_m, lse_n = res
    g_out, g_lse = g
    b, t, h, d = q.shape
    bh = b * h

    def bhq1_to_bqh1(x):
        return x.transpose(0, 2, 1, 3)

    go = g_out.astype(jnp.float32)
    a_p = jnp.exp2(prev_lse.astype(jnp.float32) - lse_m)   # [B,H,T,1]
    a_n = jnp.exp2(lse_n.reshape(b, h, t, 1) - lse_m)

    def rowsum(x, y):            # [B,T,H,D] ⊙ [B,T,H,D] -> [B,H,T,1]
        return jnp.sum(x * y.astype(jnp.float32), axis=-1,
                       keepdims=True).transpose(0, 2, 1, 3)

    r_m = rowsum(go, out_m)
    r_p = rowsum(go, prev_out)
    d_prev_out = go * bhq1_to_bqh1(a_p)
    d_o_n = g_out * bhq1_to_bqh1(a_n).astype(g_out.dtype)
    d_prev_lse = _LN2 * a_p * (r_p - r_m) + g_lse * a_p
    d_lse_n = _LN2 * a_p * (r_m - r_p) + g_lse * a_n
    delta_n = r_m - a_p * r_p

    dq, dk, dv = _bwd(
        sm_scale, causal, block_q, block_k, interpret,
        (q, k, v, None, lse_n), d_o_n,
        dlse=d_lse_n.reshape(bh, t, 1), pack=pack,
        delta=delta_n.reshape(bh, t, 1))
    return dq, dk, dv, d_prev_out, d_prev_lse


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
    return _flash_merge(q, k, v, prev_out.astype(jnp.float32),
                        prev_lse, *args)


# ----------------------------------------------------------------------
# remat-friendly form: never re-run the forward kernel in backward
# ----------------------------------------------------------------------
# Under `jax.checkpoint`, a custom_vjp op is atomic: the backward pass
# re-runs its FORWARD to regenerate residuals, so rematted transformer
# blocks pay the (expensive) flash forward kernel twice.
# The split below routes the residuals AROUND the remat boundary:
#
#     out, lse = _flash_outlse(q, k, v)      # fwd kernel, NOT differentiable
#     out = checkpoint_name(out, "attn_out") # 2 B/elem per layer
#     lse = checkpoint_name(lse, "attn_lse") # 4 B/token per layer
#     out = _flash_apply(q, k, v, out, lse)  # identity fwd; custom bwd
#
# With a `save_only_these_names:attn_out,attn_lse` policy the named
# values are saved, `_flash_outlse` is dead in the recompute (its only
# outputs are saved) and never re-runs, while `_flash_apply`'s VJP runs
# the dq/dkv kernels directly from the saved residuals — q, k, v are
# recomputed by the (cheap) qkv-matmul chain remat. Without such a
# policy the behavior degrades gracefully to plain full remat.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_apply(q, k, v, out, lse, sm_scale, causal, block_q, block_k,
                 interpret, pack):
    return out


def _flash_apply_fwd(q, k, v, out, lse, sm_scale, causal, block_q,
                     block_k, interpret, pack):
    return out, (q, k, v, out, lse)


def _flash_apply_bwd(sm_scale, causal, block_q, block_k, interpret, pack,
                     res, g):
    dq, dk, dv = _bwd(sm_scale, causal, block_q, block_k, interpret,
                      res, g, pack=pack)
    # out/lse enter via the non-differentiable forward kernel (gradient
    # flows exclusively through q, k, v — mathematically out = f(q,k,v))
    return dq, dk, dv, jnp.zeros_like(res[3]), jnp.zeros_like(res[4])


_flash_apply.defvjp(_flash_apply_fwd, _flash_apply_bwd)


def _normalize_flash_args(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret, head_packing="auto"):
    """Shared argument validation/defaulting for all flash entry
    points — they must never diverge (the rematerializable form
    guarantees identical numerics)."""
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


def flash_attention_rematerializable(q, k, v, causal=True, sm_scale=None,
                                     block_q=None,
                                     block_k=None,
                                     interpret=None, head_packing="auto"):
    """flash_attention whose (out, lse) carry checkpoint_name
    annotations ("attn_out"/"attn_lse") so a names-saving remat policy
    skips the forward-kernel re-run in backward. Numerics identical to
    `flash_attention`."""
    from jax.ad_checkpoint import checkpoint_name
    b, t, h, d = q.shape
    args = _normalize_flash_args(q, k, v, causal, sm_scale, block_q,
                                 block_k, interpret, head_packing)

    out, lse = _fwd(jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
                    jax.lax.stop_gradient(v), *args)
    out = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return _flash_apply(q, k, v, out, lse, *args)


def flash_attention(q, k, v, causal=True, sm_scale=None,
                    block_q=None, block_k=None,
                    interpret=None, head_packing="auto"):
    """Flash attention over [B, T, H, D] tensors; returns [B, T, H, D].

    interpret=None auto-selects Pallas interpreter mode off-TPU so the
    same kernel code is exercised by CPU tests.  head_packing
    ("auto"|"packed"|"off") selects the two-heads-per-step K=128 kernel
    for d=64 (auto: on real TPU only; packed/off force it on/off; see
    module docstring).
    """
    return _flash(q, k, v, *_normalize_flash_args(
        q, k, v, causal, sm_scale, block_q, block_k, interpret,
        head_packing))
