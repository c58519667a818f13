"""Block-sparse flash attention: index-compacted Pallas kernels.

TPU replacement for the reference's Triton SDD/DSD/DDS matmul + sparse
softmax pipeline (`ops/sparse_attention/matmul.py:16-750`,
`softmax.py:17-304`, `trsrc/*.tr`). The reference compiles per-layout
lookup tables (`sdd_segment`, `csrc/sparse_attention/utils.cpp:117`)
that enumerate the visible blocks; the TPU kernels do the same thing
with scalar-prefetch index tables: for each q SUPER-ROW (qt adjacent
layout rows — the kernel's q tile is qt*block rows) the table lists the
union of visible key blocks, with a per-entry bitmask gating each
member row; causality is folded in at block granularity. The grid's
inner dimension runs over THAT list — `kmax` steps instead of `nq` —
so work scales with layout density, while each step is one fat
(g heads x qt*block x block) MXU tile from a regular streaming access
pattern; head-grouping and super-rows exist to amortize per-grid-step
overhead.

Tables dedupe identical per-head layouts (the default for every shipped
SparsityConfig); SMEM holds ~3*U*(nq/qt)*kmax int32 entries (indices,
counts, masks) plus the transpose tables — a few KB.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.per_device import ROWS, per_device
from deepspeed_tpu.ops.transformer.flash_attention import (NEG_INF, _on_tpu,
                                                           dense_attention)

# f32 score-tile budget per grid step and the matching Mosaic
# scoped-vmem ceiling (default 16 MB refuses ~18 MB stacks; the chip
# has 128 MB of VMEM)
_SCORE_TILE_BUDGET = 4 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024
_FWD_MIN_OUTER = 8


def _element_spec(shape, index_map):
    """All-Element BlockSpec: every index_map coordinate is an ELEMENT
    offset."""
    return pl.BlockSpec(tuple(pl.Element(s) for s in shape), index_map)


def _compiler_params(kind):
    # On v5e at 16k, before the ledger (no cell now): BACKWARD kernels want
    # ("parallel","parallel","arbitrary") (+40% over default), while
    # the forward's online-softmax carry pipelines better with Mosaic's
    # own scheduling (declared semantics cost it ~25%).
    sem = ("parallel", "parallel", "arbitrary") if kind == "bwd" else None
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=_VMEM_LIMIT)


# ----------------------------------------------------------------------
# layout -> visible-block index tables
# ----------------------------------------------------------------------
def _build_tables(layout, causal, qt):
    """Concrete [H, nq, nk] layout -> scalar-prefetch tables over
    SUPER-ROWS of `qt` consecutive layout rows (the kernel's q tile is
    qt*block rows — bigger MXU tiles, fewer grid steps):

      head_map [H]            head -> unique-layout index u
      kidx [U*nqs*kmax]       visible key blocks per q super-row (union
                              over member rows, padded)
      kcnt [U*nqs]            count per q super-row
      kmask [U*nqs*kmax]      per-entry bitmask: which of the qt member
                              rows actually sees that key block
      qidx/qcnt/qmask         the transpose (visible q super-rows per
                              key column) for the dK/dV kernel

    Causality is folded in at block granularity (ki <= qi), so the
    kernels iterate ONLY over genuinely visible tiles — the TPU analog
    of the reference's sdd_segment lookup tables. Padding repeats index
    0 with an all-zero mask."""
    lay = np.asarray(layout, np.int32)
    unique, inverse = np.unique(lay, axis=0, return_inverse=True)
    U, nq, nk = unique.shape
    assert nq % qt == 0
    nqs = nq // qt
    vis = unique != 0
    if causal:
        vis = vis & np.tril(np.ones((nq, nk), bool))[None]

    vis_s = vis.reshape(U, nqs, qt, nk)
    union = vis_s.any(axis=2)                              # [U, nqs, nk]
    bits = (vis_s.astype(np.int32) <<
            np.arange(qt)[None, None, :, None]).sum(axis=2)  # [U,nqs,nk]

    kcnt = union.sum(axis=2).astype(np.int32)              # [U, nqs]
    qcnt = union.sum(axis=1).astype(np.int32)              # [U, nk]
    kmax = max(1, int(kcnt.max()))
    qmax = max(1, int(qcnt.max()))
    kidx = np.zeros((U, nqs, kmax), np.int32)
    kmask = np.zeros((U, nqs, kmax), np.int32)
    qidx = np.zeros((U, nk, qmax), np.int32)
    qmask = np.zeros((U, nk, qmax), np.int32)
    for u in range(U):
        for R in range(nqs):
            cols = np.where(union[u, R])[0]
            kidx[u, R, :len(cols)] = cols
            kmask[u, R, :len(cols)] = bits[u, R, cols]
        for ki in range(nk):
            rows = np.where(union[u, :, ki])[0]
            qidx[u, ki, :len(rows)] = rows
            qmask[u, ki, :len(rows)] = bits[u, rows, ki]
    # head-group size: the largest power of two (<=8) dividing H whose
    # groups are layout-uniform — grouped heads ride one grid step
    hm = inverse.reshape(-1)
    H = hm.size
    g = 1
    for cand in (8, 4, 2):
        if H % cand == 0 and \
                (hm.reshape(H // cand, cand) ==
                 hm.reshape(H // cand, cand)[:, :1]).all():
            g = cand
            break
    return (jnp.asarray(hm, jnp.int32),
            jnp.asarray(kidx.reshape(-1)), jnp.asarray(kcnt.reshape(-1)),
            jnp.asarray(kmask.reshape(-1)),
            jnp.asarray(qidx.reshape(-1)), jnp.asarray(qcnt.reshape(-1)),
            jnp.asarray(qmask.reshape(-1)),
            kmax, qmax, g)


def _row(hm_ref, bhi, qi, nq, num_heads):
    u = hm_ref[jax.lax.rem(bhi, num_heads)]
    return u * nq + qi


# ----------------------------------------------------------------------
# kernels (grid inner dim = visible-block list position)
# ----------------------------------------------------------------------
def _visible_mask(mbits, R, ki, qt, block, causal):
    """[qt*block, block] bool: which score entries are visible — the
    per-member-row layout bit, intersected with the causal triangle in
    GLOBAL coordinates when causal."""
    qtb = qt * block
    rows = jax.lax.broadcasted_iota(jnp.int32, (qtb, block), 0)
    visible = ((mbits >> (rows // block)) & 1) == 1
    if causal:
        grows = R * qtb + rows
        cols = ki * block + jax.lax.broadcasted_iota(
            jnp.int32, (qtb, block), 1)
        visible = visible & (grows >= cols)
    return visible


def _bs_fwd_kernel(hm_ref, kidx_ref, kcnt_ref, kmask_ref, q_ref, k_ref,
                   v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                   sm_scale, causal, block, num_heads, nqs, kmax, g, qt,
                   lse2d):
    # blocks carry G heads x QT layout rows per grid step (legal because
    # grouped heads share one layout row): fewer, fatter steps amortize
    # the per-step grid/DMA overhead that starves small tiles; the
    # bitmask gates each member row on its own layout visibility
    R = pl.program_id(1)
    st = pl.program_id(2)
    row = _row(hm_ref, pl.program_id(0) * g, R, nqs, num_heads)
    qtb = qt * block

    @pl.when(st == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(st < kcnt_ref[row])
    def _():
        ki = kidx_ref[row * kmax + st]
        mbits = kmask_ref[row * kmax + st]
        q = q_ref[...]
        k = k_ref[...]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale  # [G, QTB, B]
        s = jnp.where(
            _visible_mask(mbits, R, ki, qt, block, causal)[None],
            s, NEG_INF)

        m_prev = m_scr[:, :, :1]
        l_prev = l_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # rows with no visible block this step keep m=-inf; guard exp
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0))
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[...]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:, :, :1] = m_new
        l_scr[:, :, :1] = l_new

    @pl.when(st == kmax - 1)
    def _():
        l = l_scr[:, :, :1]
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[...] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # A member row with ZERO visible entries (possible inside a
        # super-row whose union has blocks only for sibling rows) must
        # export lse=+inf, not NEG_INF+log(1e-30): the backward kernels
        # compute p=exp(s-lse) and only +inf sends every masked score to
        # exactly 0 (delta=0 does not cancel the dp term).
        # lse rides [g, qtb] when the head group allows it — t in the
        # MINOR dim (a [.., t, 1] layout pads the 1-wide minor to full
        # 128-lane tiles: 128x the write bytes)
        lse_val = jnp.where(l > 0.0, m_scr[:, :, :1] + jnp.log(l_safe),
                            jnp.inf)
        if lse2d:
            lse_ref[...] = lse_val[:, :, 0]
        else:
            lse_ref[...] = lse_val


def _bs_bwd_dkv_kernel(hm_ref, qidx_ref, qcnt_ref, qmask_ref, q_ref,
                       k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                       dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                       block, num_heads, nqs, qmax, g, qt, lse2d):
    ki = pl.program_id(1)
    st = pl.program_id(2)
    # the q-side tables for dK/dV are indexed by KEY column: nk == nq
    # rows in the flat [U, nk] layout (square layouts asserted)
    row = _row(hm_ref, pl.program_id(0) * g, ki, nqs * qt, num_heads)
    qtb = qt * block

    @pl.when(st == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(st < qcnt_ref[row])
    def _():
        R = qidx_ref[row * qmax + st]
        mbits = qmask_ref[row * qmax + st]
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...][..., None] if lse2d else lse_ref[...]
        delta = delta_ref[...][..., None] if lse2d else delta_ref[...]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale  # [G,QTB,B]
        s = jnp.where(
            _visible_mask(mbits, R, ki, qt, block, causal)[None],
            s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(st == qmax - 1)
    def _():
        dk_ref[...] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[:].astype(dv_ref.dtype)


def _bs_bwd_dq_kernel(hm_ref, kidx_ref, kcnt_ref, kmask_ref, q_ref,
                      k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                      dq_scr, *, sm_scale, causal, block, num_heads,
                      nqs, kmax, g, qt, lse2d):
    R = pl.program_id(1)
    st = pl.program_id(2)
    row = _row(hm_ref, pl.program_id(0) * g, R, nqs, num_heads)
    qtb = qt * block

    @pl.when(st == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(st < kcnt_ref[row])
    def _():
        ki = kidx_ref[row * kmax + st]
        mbits = kmask_ref[row * kmax + st]
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...][..., None] if lse2d else lse_ref[...]
        delta = delta_ref[...][..., None] if lse2d else delta_ref[...]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(
            _visible_mask(mbits, R, ki, qt, block, causal)[None],
            s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(st == kmax - 1)
    def _():
        dq_ref[...] = dq_scr[:].astype(dq_ref.dtype)


# ----------------------------------------------------------------------
# pallas_call plumbing
# ----------------------------------------------------------------------
def _k_lookup(nqs, kmax, num_heads, g):
    """BlockSpec index fn for k/v: the key block comes from the table."""
    def idx(grp, R, st, hm_ref, kidx_ref, kcnt_ref, kmask_ref):
        row = _row(hm_ref, grp * g, R, nqs, num_heads)
        return (grp, kidx_ref[row * kmax + st], 0)
    return idx


def _q_lookup(nk, qmax, num_heads, g):
    def idx(grp, ki, st, hm_ref, qidx_ref, qcnt_ref, qmask_ref):
        row = _row(hm_ref, grp * g, ki, nk, num_heads)
        return (grp, qidx_ref[row * qmax + st], 0)
    return idx


def _bs_fwd(q, k, v, head_map, kidx, kcnt, kmask, sm_scale, causal,
            block, interpret, kmax, g, qt, allow_lse2d=True):
    b, t, h, d = q.shape
    bh = b * h
    nqs = t // block // qt
    qtb = qt * block

    def to_bht(x):
        return x.transpose(0, 2, 1, 3).reshape(bh, t, d)

    lse2d = (g % 8 == 0) and allow_lse2d   # 2-D lse needs sublane-divisible g
    kernel = functools.partial(_bs_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, block=block, num_heads=h,
                               nqs=nqs, kmax=kmax, g=g, qt=qt,
                               lse2d=lse2d)
    fixed = lambda grp, R, st, *_: (grp, R, 0)
    fixed2 = lambda grp, R, st, *_: (grp, R)
    kv = _k_lookup(nqs, kmax, h, g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(bh // g, nqs, kmax),
        in_specs=[
            pl.BlockSpec((g, qtb, d), fixed),
            pl.BlockSpec((g, block, d), kv),
            pl.BlockSpec((g, block, d), kv),
        ],
        out_specs=[
            pl.BlockSpec((g, qtb, d), fixed),
            pl.BlockSpec((g, qtb), fixed2) if lse2d else
            pl.BlockSpec((g, qtb, 1), fixed),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, qtb, 128), jnp.float32),
            pltpu.VMEM((g, qtb, 128), jnp.float32),
            pltpu.VMEM((g, qtb, d), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        name="block_sparse_fwd",
        grid_spec=grid_spec,
        compiler_params=_compiler_params("fwd"),
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t) if lse2d else (bh, t, 1),
                                 jnp.float32),
        ],
        interpret=interpret,
    )(head_map, kidx, kcnt, kmask, to_bht(q), to_bht(k), to_bht(v))
    return out, lse


def _bs_bwd(sm_scale, causal, block, interpret, kmax, qmax, g_grp, qt,
            res, g):
    (q, k, v, out, lse, head_map, kidx, kcnt, kmask, qidx, qcnt,
     qmask) = res
    b, t, h, d = q.shape
    bh = b * h
    nk = t // block
    nqs = nk // qt
    qtb = qt * block

    def to_bht(x):
        return x.transpose(0, 2, 1, 3).reshape(bh, t, d)

    def from_bht(x):
        return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    qt_, kt, vt, dot_ = to_bht(q), to_bht(k), to_bht(v), to_bht(g)
    ot = to_bht(out)
    lse2d = (lse.ndim == 2)
    delta = jnp.sum(dot_.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1, keepdims=not lse2d)

    fixed1 = lambda grp, ki, st, *_: (grp, ki, 0)
    qv = _q_lookup(nk, qmax, h, g_grp)
    qv2 = lambda grp, ki, st, *refs: qv(grp, ki, st, *refs)[:2]
    dkv_kernel = functools.partial(_bs_bwd_dkv_kernel, sm_scale=sm_scale,
                                   causal=causal, block=block,
                                   num_heads=h, nqs=nqs, qmax=qmax,
                                   g=g_grp, qt=qt, lse2d=lse2d)
    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(bh // g_grp, nk, qmax),
        in_specs=[
            pl.BlockSpec((g_grp, qtb, d), qv),      # q super-row
            pl.BlockSpec((g_grp, block, d), fixed1),  # k at ki
            pl.BlockSpec((g_grp, block, d), fixed1),  # v at ki
            pl.BlockSpec((g_grp, qtb, d), qv),      # do super-row
            (pl.BlockSpec((g_grp, qtb), qv2) if lse2d else
             pl.BlockSpec((g_grp, qtb, 1), qv)),    # lse super-row
            (pl.BlockSpec((g_grp, qtb), qv2) if lse2d else
             pl.BlockSpec((g_grp, qtb, 1), qv)),    # delta super-row
        ],
        out_specs=[
            pl.BlockSpec((g_grp, block, d), fixed1),
            pl.BlockSpec((g_grp, block, d), fixed1),
        ],
        scratch_shapes=[
            pltpu.VMEM((g_grp, block, d), jnp.float32),
            pltpu.VMEM((g_grp, block, d), jnp.float32),
        ],
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="block_sparse_bwd_dkv",
        grid_spec=dkv_spec,
        compiler_params=_compiler_params("bwd"),
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        interpret=interpret,
    )(head_map, qidx, qcnt, qmask, qt_, kt, vt, dot_, lse, delta)

    fixed = lambda grp, R, st, *_: (grp, R, 0)
    kv = _k_lookup(nqs, kmax, h, g_grp)
    dq_kernel = functools.partial(_bs_bwd_dq_kernel, sm_scale=sm_scale,
                                  causal=causal, block=block,
                                  num_heads=h, nqs=nqs, kmax=kmax,
                                  g=g_grp, qt=qt, lse2d=lse2d)
    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(bh // g_grp, nqs, kmax),
        in_specs=[
            pl.BlockSpec((g_grp, qtb, d), fixed),
            pl.BlockSpec((g_grp, block, d), kv),
            pl.BlockSpec((g_grp, block, d), kv),
            pl.BlockSpec((g_grp, qtb, d), fixed),
            (pl.BlockSpec((g_grp, qtb), lambda grp, R, st, *_: (grp, R))
             if lse2d else pl.BlockSpec((g_grp, qtb, 1), fixed)),
            (pl.BlockSpec((g_grp, qtb), lambda grp, R, st, *_: (grp, R))
             if lse2d else pl.BlockSpec((g_grp, qtb, 1), fixed)),
        ],
        out_specs=pl.BlockSpec((g_grp, qtb, d), fixed),
        scratch_shapes=[pltpu.VMEM((g_grp, qtb, d), jnp.float32)],
    )
    dq = pl.pallas_call(
        dq_kernel,
        name="block_sparse_bwd_dq",
        grid_spec=dq_spec,
        compiler_params=_compiler_params("bwd"),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=interpret,
    )(head_map, kidx, kcnt, kmask, qt_, kt, vt, dot_, lse, delta)

    return (from_bht(dq), from_bht(dk), from_bht(dv),
            None, None, None, None, None, None, None)


# ----------------------------------------------------------------------
# band + global fast path (Longformer/Fixed-class layouts)
# ----------------------------------------------------------------------
def _band_decompose(layout, causal, max_globals=64, max_band_blocks=64):
    """Causal-folded layout -> ("sliding"|"aligned", w, global_cols)
    when it is EXACTLY a width-w block window (sliding band, or
    window-ALIGNED block-diagonal groups — the reference Fixed
    pattern's "local" attention, `sparsity_config.py:94`) plus a set
    of globally-visible block columns; None otherwise (BigBird random
    blocks, per-head layouts).

    BSLongformer decomposes as sliding, Fixed as aligned; the fast
    forward then replaces the per-visible-block table walk with ONE
    contiguous band/window fetch + regular tiles over the gathered
    global columns — far fewer, far fatter grid steps."""
    lay = np.asarray(layout, np.int32)
    if lay.ndim == 3:
        if not (lay == lay[:1]).all():
            return None            # per-head layouts: table path
        lay = lay[0]
    vis = lay != 0
    nq = vis.shape[0]
    if causal:
        vis = vis & np.tril(np.ones_like(vis, dtype=bool))
    rows_i, cols_j = np.nonzero(vis)
    # global columns: visible from EVERY (causal-)eligible row
    gcols = []
    for j in range(nq):
        rows_seeing = vis[:, j]
        expect = np.arange(nq) >= j if causal else np.ones(nq, bool)
        if (rows_seeing == expect).all():
            gcols.append(j)
    gset = set(gcols)
    if len(gcols) > max_globals:
        return None
    off_band = [(i, j) for i, j in zip(rows_i, cols_j) if j not in gset]
    ii = np.arange(nq)[:, None]
    jj = np.arange(nq)[None, :]
    tril = np.tril(np.ones_like(vis, dtype=bool))

    def matches(base):
        expected = base.copy()
        for j in gcols:
            expected[:, j] |= (np.arange(nq) >= j) if causal else True
        if causal:
            expected &= tril
        return np.array_equal(vis, expected)

    # (a) sliding band of width w
    w = max((i - j + 1 for i, j in off_band), default=1)
    if w <= max_band_blocks:
        band = (jj <= ii) & (jj >= ii - w + 1) if causal else \
            (np.abs(ii - jj) < w)
        if matches(band):
            return "sliding", int(w), tuple(int(j) for j in gcols)
    # (b) window-aligned block-diagonal of width w: row i sees cols of
    # its own window floor(i/w) (the Fixed pattern's local part). The
    # minimal candidate w comes from the same max-offset statistic.
    for wa in range(max(w, 1), max_band_blocks + 1):
        aligned = (ii // wa) == (jj // wa)
        if matches(aligned):
            return "aligned", int(wa), tuple(int(j) for j in gcols)
    return None


def _band_fwd_kernel(q_ref, kb_ref, vb_ref, kg_ref, vg_ref, pos_ref,
                     o_ref, lse_ref, m_scr, l_scr, acc_scr, *, sm_scale,
                     block, qt, w, n_steps, tk, g, lse2d, causal, nq,
                     BW, aligned, max_live=None):
    R = pl.program_id(1)
    st = pl.program_id(2)
    qtb = qt * block

    def online_update(s, vv):
        m_prev = m_scr[:, :, :1]
        l_prev = l_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0))
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vv.dtype), vv, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:, :, :1] = m_new
        l_scr[:, :, :1] = l_new

    @pl.when(st == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        s = jax.lax.dot_general(
            q_ref[...], kb_ref[...], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale
        # band/window start (block units) — must mirror the index map
        if aligned:
            S = jnp.clip((R * qt) // w * w, 0, nq - BW)
        else:
            S = jnp.clip(R * qt - (w - 1), 0, nq - BW)
        rows = jax.lax.broadcasted_iota(jnp.int32, (qtb, BW * block), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (qtb, BW * block), 1)
        gp = R * qtb + rows
        kp = S * block + cols
        if aligned:
            # window-aligned local attention (Fixed): same w-window only
            visible = (kp // block // w) == (gp // block // w)
            if causal:
                visible = visible & (kp <= gp)
        else:
            visible = (kp // block) >= (gp // block - (w - 1))
            if causal:
                visible = visible & (kp <= gp)
            else:
                visible = visible & \
                    ((kp // block) <= (gp // block + (w - 1)))
        s = jnp.where(visible[None], s, NEG_INF)
        online_update(s, vb_ref[...])

    # causal: gathered global columns are position-sorted, so a tile
    # whose FIRST position exceeds the super-row's last query position
    # is fully invisible — skip its matmul outright (for the Fixed
    # pattern the per-row visible-summary count grows with position,
    # and this turns the global sweep's triangular waste into skipped
    # steps, ~halving global work at long T). With the regular-globals
    # index clamp (`max_live`) the liveness MUST come from the closed
    # form: dead steps re-fetch the last LIVE tile (so Pallas elides
    # the DMA), whose pos entries would wrongly pass the runtime test.
    tile_live = True
    if causal:
        if max_live is not None:
            tile_live = st - 1 <= max_live(R)
        else:
            tile_live = pos_ref[0, 0] <= (R + 1) * qtb - 1

    @pl.when(jnp.logical_and(st > 0, tile_live))
    def _():
        s = jax.lax.dot_general(
            q_ref[...], kg_ref[...], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale
        pos = pos_ref[0, :]                       # [tk] source positions
        rows = jax.lax.broadcasted_iota(jnp.int32, (qtb, tk), 0)
        gp = R * qtb + rows
        # exclude entries the band/window step already covered (double
        # count) and the zero-K padding tail (pos is 2**30 there —
        # without the bound it would pass the non-causal test and add
        # phantom mass)
        valid = pos[None, :] < nq * block
        if aligned:
            other_window = (pos[None, :] // block // w) != \
                (gp // block // w)
            if causal:
                visible = other_window & (pos[None, :] <= gp) & valid
            else:
                visible = other_window & valid
        elif causal:
            visible = ((pos[None, :] // block) < (gp // block - (w - 1))) \
                & (pos[None, :] <= gp) & valid
        else:
            diff = pos[None, :] // block - gp // block
            visible = ((diff < -(w - 1)) | (diff > (w - 1))) & valid
        s = jnp.where(visible[None], s, NEG_INF)
        online_update(s, vg_ref[...])

    @pl.when(st == n_steps - 1)
    def _():
        l = l_scr[:, :, :1]
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[...] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_val = jnp.where(l > 0.0, m_scr[:, :, :1] + jnp.log(l_safe),
                            jnp.inf)
        if lse2d:
            lse_ref[...] = lse_val[:, :, 0]
        else:
            lse_ref[...] = lse_val


def _band_fwd(q, k, v, band, sm_scale, causal, block, interpret, qt,
              allow_lse2d=True):
    """(out [bh,t,d], lse) via the band+global forward. allow_lse2d:
    the BACKWARD (table kernels, head group g_bwd) must also be able to
    address a 2-D lse — callers pass g_bwd's sublane divisibility."""
    kind, w, gcols = band
    aligned = kind == "aligned"
    b, t, h, d = q.shape
    bh = b * h
    nq = t // block
    nqs = nq // qt
    qtb = qt * block
    if aligned:
        # caller guarantees qt % w == 0 or w % qt == 0, so a q
        # super-row's member windows span exactly max(w, qt) block cols
        assert qt % w == 0 or w % qt == 0, (qt, w)
        BW = min(nq, max(w, qt))
    else:
        BW = min(nq, (w + qt - 1) if causal else (2 * w + qt - 2))

    def to_bht(x):
        return x.transpose(0, 2, 1, 3).reshape(bh, t, d)

    qb, kb, vb = to_bht(q), to_bht(k), to_bht(v)

    # gathered global columns (+1 tile of padding when empty); positions
    # beyond t mask to invisible
    tk = min(1024, max(block, 512))
    if gcols:
        gidx = np.concatenate(
            [np.arange(block) + j * block for j in gcols])
        pos = gidx.astype(np.int32)
    else:
        gidx = np.zeros((0,), np.int64)
        pos = np.zeros((0,), np.int32)
    ng = len(gidx)
    pad = (-ng) % tk if ng else tk
    n_steps = 1 + (ng + pad) // tk if ng else 1
    kg = jnp.pad(kb[:, gidx, :], ((0, 0), (0, pad), (0, 0))) if ng else \
        jnp.zeros((bh, tk, d), kb.dtype)
    vg = jnp.pad(vb[:, gidx, :], ((0, 0), (0, pad), (0, 0))) if ng else \
        jnp.zeros((bh, tk, d), vb.dtype)
    pos = jnp.asarray(
        np.pad(pos, (0, pad if ng else tk),
               constant_values=np.int32(2**30)))[None, :]   # [1, NGB]

    # head group: fattest that fits the band score tile (<= ~20 MB under
    # the raised scoped-vmem limit); prefer sublane-divisible g for the
    # 2-D lse layout
    g = 1
    while (g * 2 <= 8 and bh % (g * 2) == 0 and
           g * 2 * qtb * BW * block * 4 <= 24 * 1024 * 1024):
        g *= 2
    lse2d = (g % 8 == 0) and allow_lse2d

    # Regularly-spaced globals (the Fixed pattern: one summary column
    # per w-block window => gcols is the stride-w progression ending
    # each window) admit a CLOSED FORM for "last live global tile of
    # super-row R" under causality: tile sti's first source position is
    # sti*(tk//block)*w*block + (w-1)*block. Clamping the index maps to
    # that bound makes dead steps refetch the PREVIOUS tile — which
    # Pallas elides as a revisit — so causally dead tiles cost neither
    # MXU nor DMA (review r4: the in-kernel guard alone still streamed
    # g*tk*d*2 bytes of K and V per dead step).
    regular_globals = bool(
        causal and gcols and tk % block == 0 and
        tuple(gcols) == tuple(w - 1 + m * w for m in range(len(gcols))))
    blocks_per_tile = tk // block if tk % block == 0 else 0

    def max_live_tile(R):
        # largest sti with first_pos(sti) <= (R+1)*qtb - 1, in 0-based
        # global-tile units (st = sti + 1 in the grid)
        return ((R + 1) * qtb - 1 - (w - 1) * block) // \
            (blocks_per_tile * w * block)

    kernel = functools.partial(
        _band_fwd_kernel, sm_scale=sm_scale, block=block, qt=qt, w=w,
        n_steps=n_steps, tk=tk, g=g, lse2d=lse2d, causal=causal, nq=nq,
        BW=BW, aligned=aligned,
        max_live=max_live_tile if regular_globals else None)

    def band_idx(grp, R, st):
        # all-Element spec (Mosaic rejects mixed Element/Blocked dims):
        # every coordinate is an ELEMENT offset
        if aligned:
            start = jnp.clip((R * qt) // w * w, 0, nq - BW)
        else:
            start = jnp.clip(R * qt - (w - 1), 0, nq - BW)
        return (grp * g, start * block, 0)

    def gtile(R, st):
        sti = jnp.maximum(st - 1, 0)
        if regular_globals:
            sti = jnp.clip(sti, 0, jnp.maximum(max_live_tile(R), 0))
        return sti

    def gtile_idx(grp, R, st):
        return (grp, gtile(R, st), 0)

    out, lse = pl.pallas_call(
        kernel,
        name="block_sparse_band_fwd",
        grid=(bh // g, nqs, n_steps),
        in_specs=[
            pl.BlockSpec((g, qtb, d), lambda grp, R, st: (grp, R, 0)),
            _element_spec((g, BW * block, d), band_idx),
            _element_spec((g, BW * block, d), band_idx),
            pl.BlockSpec((g, tk, d), gtile_idx),
            pl.BlockSpec((g, tk, d), gtile_idx),
            pl.BlockSpec((1, tk), lambda grp, R, st: (0, gtile(R, st))),
        ],
        out_specs=[
            pl.BlockSpec((g, qtb, d), lambda grp, R, st: (grp, R, 0)),
            (pl.BlockSpec((g, qtb), lambda grp, R, st: (grp, R))
             if lse2d else
             pl.BlockSpec((g, qtb, 1), lambda grp, R, st: (grp, R, 0))),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, qtb, 128), jnp.float32),
            pltpu.VMEM((g, qtb, 128), jnp.float32),
            pltpu.VMEM((g, qtb, d), jnp.float32),
        ],
        compiler_params=_compiler_params("fwd"),
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t) if lse2d else (bh, t, 1),
                                 jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb, kg, vg, pos)
    return out, lse


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(10, 11, 12, 13, 14, 15, 16, 17, 18))
def _bs_flash(q, k, v, head_map, kidx, kcnt, kmask, qidx, qcnt, qmask,
              sm_scale, causal, block, interpret, kmax, qmax, g, qt,
              band):
    if band is not None:
        out, _ = _band_fwd(q, k, v, band, sm_scale, causal, block,
                           interpret, qt, allow_lse2d=(g[1] % 8 == 0))
    else:
        out, _ = _bs_fwd(q, k, v, head_map, kidx, kcnt, kmask, sm_scale,
                         causal, block, interpret, kmax, g[0], qt,
                         allow_lse2d=(g[1] % 8 == 0))
    b, t, h, d = q.shape
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _bs_flash_fwd(q, k, v, head_map, kidx, kcnt, kmask, qidx, qcnt,
                  qmask, sm_scale, causal, block, interpret, kmax, qmax,
                  g, qt, band):
    if band is not None:
        out, lse = _band_fwd(q, k, v, band, sm_scale, causal, block,
                             interpret, qt, allow_lse2d=(g[1] % 8 == 0))
    else:
        out, lse = _bs_fwd(q, k, v, head_map, kidx, kcnt, kmask,
                           sm_scale, causal, block, interpret, kmax,
                           g[0], qt, allow_lse2d=(g[1] % 8 == 0))
    b, t, h, d = q.shape
    out_bthd = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return out_bthd, (q, k, v, out_bthd, lse, head_map, kidx, kcnt,
                      kmask, qidx, qcnt, qmask)


def _bs_flash_bwd(sm_scale, causal, block, interpret, kmax, qmax, g_grp,
                  qt, band, res, g):
    # the backward always runs the table kernels — they are fast (short
    # carries, fat tiles) and layout-general; only the forward has a
    # band+global specialization
    return _bs_bwd(sm_scale, causal, block, interpret, kmax, qmax,
                   g_grp[1], qt, res, g)


_bs_flash.defvjp(_bs_flash_fwd, _bs_flash_bwd)


def layout_to_dense_mask(layout, seq_len, block):
    """[H, nq, nk] block layout -> [H, T, T] boolean mask (the XLA
    fallback path and the ground truth for kernel tests)."""
    lay = np.asarray(layout, bool)
    return np.kron(lay, np.ones((block, block), dtype=bool))


def block_sparse_attention(q, k, v, layout, block, causal=False,
                           sm_scale=None, interpret=None,
                           head_packing="auto"):
    """Block-sparse attention over [B, T, H, D].

    layout: [H, T/block, T/block] 0/1 matrix from a SparsityConfig.

    head_packing: accepted for signature parity with the dense flash
    kernel ("auto"|"packed"|"off") but the sparse kernels ALWAYS run
    unpacked — the index-compacted tables are per-head (each head has
    its own visible-block list), so pairing two heads into one K=128
    contraction would force both onto the union of their layouts.
    "auto"/"off" silently take the unpacked sparse kernel; "packed"
    raises (use the dense kernel for packed d=64 attention).
    """
    b, t, h, d = q.shape
    if head_packing in ("packed", True, 1):
        raise ValueError(
            "head_packing='packed' is not supported by the block-sparse "
            "kernels (per-head visible-block tables don't pair); use "
            "'auto'/'off', or the dense flash kernel for packed "
            "attention")
    if head_packing not in ("auto", "off", None, False, 0):
        raise ValueError(
            f"head_packing={head_packing!r}: expected 'auto' or 'off'")
    if isinstance(layout, jax.core.Tracer):
        raise ValueError(
            "block_sparse_attention requires a CONCRETE layout (it is "
            "compiled into visible-block index tables host-side); build "
            "the layout outside jit — SparsityConfig.make_layout "
            "returns numpy and layouts are static per (config, seq_len)")
    layout = np.asarray(layout)
    assert layout.shape == (h, t // block, t // block), \
        (layout.shape, (h, t // block, t // block))
    assert t % block == 0
    # every query block must see at least one key block (the diagonal in
    # all shipped patterns) or its softmax is over the empty set
    if causal:
        diag = layout[:, np.arange(t // block), np.arange(t // block)]
        assert diag.all(), "causal layouts must include the diagonal"
    else:
        assert (layout.sum(-1) > 0).all(), \
            "every query block needs >= 1 visible key block"
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    if interpret is None:
        interpret = not _on_tpu()
    # q super-tile: target ~512 query rows per grid step; must divide
    # the block-row count. Head-group g then fits the VMEM tile budget.
    nq = t // block
    qt = max(1, min(4, 512 // block, nq))
    while nq % qt != 0:
        qt -= 1
    # VMEM tile budget: the f32 score tile is g*qt*block*block*4 bytes
    # and operands are double-buffered; the pallas_calls raise the
    # Mosaic scoped-vmem limit (_VMEM_LIMIT) so fat head-groups fit —
    # bigger tiles amortize the per-grid-step fixed cost that dominates
    # short visible-block lists. qt shrinks before the tables are built
    # (tables are qt-dependent); g shrinks after.
    while qt > 1 and qt * block * block * 4 > _SCORE_TILE_BUDGET:
        qt -= 1
    while qt > 1 and nq % qt != 0:
        qt -= 1
    band = _band_decompose(layout, causal)
    if band is not None and band[0] == "aligned":
        # the aligned-window kernel needs super-rows that tile whole
        # windows (or windows that tile super-rows)
        w = band[1]
        while qt > 1 and not (qt % w == 0 or w % qt == 0):
            qt -= 1
        while qt > 1 and nq % qt != 0:
            qt -= 1
        if not (qt % w == 0 or w % qt == 0):
            band = None           # qt=1 divides everything; defensive
    (head_map, kidx, kcnt, kmask, qidx, qcnt, qmask, kmax, qmax,
     g) = _build_tables(layout, causal, qt)
    assert h % g == 0 and (b * h) % g == 0  # _build_tables guarantees
    while g > 1 and g * qt * block * block * 4 > _SCORE_TILE_BUDGET:
        g //= 2
    # The fwd kernel's online-softmax carry serializes its inner loop,
    # so it wants OUTER parallelism (many small head-groups keep the
    # pipeline full at small batch); the bwd kernels have shorter
    # carries and prefer the fattest tiles. Any divisor of g keeps
    # layout-uniform groups, so the two passes pick independently
    # (at 16k context, before the ledger: fwd g=2 + bwd g=8 was ~20%
    # faster than a shared g; no cell holds it now).
    g_fwd = g
    while g_fwd > 1 and (b * h) // g_fwd < _FWD_MIN_OUTER:
        g_fwd //= 2
    def local(q, k, v, *tables):
        return (_bs_flash(q, k, v, *tables, float(sm_scale), bool(causal),
                          int(block), bool(interpret), kmax, qmax,
                          (g_fwd, g), qt, band),)

    # on a mesh each device attends over its own batch rows (forward,
    # backward and the lse between them all stay on the device); heads
    # are held whole because the tables index them
    bthd = (ROWS, None, None, None)
    out, = per_device(local, in_dims=(bthd,) * 3 + ((None,),) * 7,
                      out_dims=(bthd,))(
        q, k, v, head_map, kidx, kcnt, kmask, qidx, qcnt, qmask)
    return out


def block_sparse_attention_dense_fallback(q, k, v, layout, block,
                                          causal=False, sm_scale=None):
    """Dense reference: same math via an expanded additive mask."""
    t = q.shape[1]
    mask = layout_to_dense_mask(layout, t, block)         # [H, T, T]
    additive = np.where(mask, 0.0, NEG_INF).astype(np.float32)
    return dense_attention(q, k, v, mask=jnp.asarray(additive)[None],
                           causal=causal, sm_scale=sm_scale)
