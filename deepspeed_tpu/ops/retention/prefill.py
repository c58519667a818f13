"""A prefill launch of gated power retention with phi kept in VMEM
(Pallas TPU kernel): `retention_chunked`'s arithmetic for one slot's
launch of T tokens, `chunk` at a time, on layer `li` and slot `slot`
of the WHOLE state arrays as they ride in the layer scan's carry,

    S : [n_layer, slots, Hk, D, d]      D = state_dim(d), d on the lanes

Grid over the key/value heads. A head's state [D, d] is copied HBM ->
VMEM once a launch, stays there over the launch's chunks and goes back
once, to where it lay (`input_output_aliases`: nothing of the array
but that layer's slot is touched, and nothing is copied). XLA's
chunked form made phi(Q) of a chunk, [C, Hq, D] float32, in HBM and
passed over it several times; here no array with a dimension of D
exists outside VMEM but the state.

Per chunk, float32 throughout, with the token axis on the lanes:

    inside the chunk   a[i, t] = (s k_i . q_t)^2 decay[i, t]  (no phi)
                       num[:, t] = V^T a,  den[t] = sum_i a[i, t]
    from the state     num[:, t] += S^T (phi(q_t) e_t)
    into the state     S = carry S + (phi(k_i) left_i) V

phi is formed a tile of eight state rows at a time on the vector unit
from the rows' order (`retention.state_dim`): runs of fixed i with j
ascending from 8 (i // 8), every run on an 8-row boundary. With
qu = sqrt(sqrt(2) s) q the rows (i, j .. j + 7) of phi(q)^T are rows
j .. j + 7 of qu^T [d, tokens] times row i of it; only a run's first
tile differs (rows j < i are padding, row j = i has c_ii = 1), which a
mask of 0 / 2^-1/2 / 1 by row restores. The tiles of 256 consecutive
state rows (a window, which may span runs) go to the matrix unit at
once, so it contracts over its whole depth whatever the runs' lengths.
The products keep `retention_chunked`'s precision: float32 operands
and sums, `HIGHEST`.

A first launch (`start == 0`) starts from zero state whatever the
block holds; a chunk that holds no valid token is skipped (it would
leave the state as it was). The gates' cumulative sums, the normaliser
z (1/d of the bytes; phi(q).z = q^T Z q with Z the [d, d] form of z,
so it needs no phi either) and the final divide stay in XLA beside
the call.

Mosaic takes the kernel where it takes decode's (`decode.usable`); the
Pallas interpreter takes any d that is a multiple of 8, and a state of
any type (read as float32, written back in its own).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.retention import decode
from deepspeed_tpu.ops.retention.retention import (HIGHEST, LANE, RUN_ALIGN,
                                                   _runs, retention_chunked,
                                                   state_dim)
from deepspeed_tpu.utils.scopes import SCOPE_STATE_RESET

f32 = jnp.float32
# rows of `gates`: exp(cum) of the chunk's tokens (once a query head of
# the group), what is left of a token's weight at the chunk's end, and
# the chunk's whole decay on every lane
_E, _LEFT, _CARRY = 0, 1, 2
# both blocks of state, in and out, double-buffered (4 x 4.5 MB at
# d = 128), a launch's operands and outputs, and room for the compiler
_VMEM_LIMIT = 64 * 1024 * 1024
# state rows a product with the matrix unit contracts over (the most
# that divides the rows: 8,704 = 34 x 256), and the tiles of eight rows
# whose phi is formed in one straight run of code
_WINDOW, _UNROLL = 256, 8


@functools.lru_cache(maxsize=None)
def _tiles(d):
    """(the run i, the first j) of every tile of eight state rows, in
    the rows' order."""
    pairs = [(i, j) for i, j0 in _runs(d, RUN_ALIGN)
             for j in range(j0, d, RUN_ALIGN)]
    return tuple(np.asarray(x, np.int32) for x in zip(*pairs))


def _kernel(li_ref, slot_ref, first_ref, live_ref, run_ref, col_ref,
            qt_ref, k_ref, kt_ref, v_ref, vt_ref, decay_ref, gates_ref,
            s_ref, o_ref, num_ref, den_ref,
            qu_ref, que_ref, ku_ref, kue_ref, v32_ref, a_ref, phiq_ref,
            phik_ref, *, d, groups, chunk, window, scale):
    n_rows = s_ref.shape[3]
    gc = groups * chunk
    tiles = window // RUN_ALIGN
    unroll = math.gcd(tiles, _UNROLL)
    root = f32(np.sqrt(np.sqrt(2.0) * scale))
    rows = lambda w: pl.ds(pl.multiple_of(w * window, window), window)
    rowid = jax.lax.broadcasted_iota(jnp.int32, (RUN_ALIGN, chunk), 0)

    # the state the launch starts from, into the block that goes back
    def bring(w, carry):
        o_ref[0, 0, 0, rows(w), :] = jnp.where(
            first_ref[0] != 0, jnp.zeros((window, d), o_ref.dtype),
            s_ref[0, 0, 0, rows(w), :])
        return carry
    jax.lax.fori_loop(0, n_rows // window, bring, 0)

    def one_chunk(c):
        gates = gates_ref[0, c]
        for g in range(groups):                              # [G, d, C]
            at = slice(g * chunk, (g + 1) * chunk)
            qu_ref[g] = qt_ref[0, c, :, at].astype(f32) * root
            que_ref[g] = qu_ref[g] * gates[_E:_E + 1, :chunk]
        ku_ref[...] = kt_ref[0, c].astype(f32) * root        # [d, C]
        kue_ref[...] = ku_ref[...] * gates[_LEFT:_LEFT + 1, :chunk]
        v32_ref[...] = v_ref[0, c].astype(f32)               # [C, d]
        carry = jnp.broadcast_to(gates[_CARRY:_CARRY + 1, :d], (window, d))
        # inside the chunk: every pair, keys on the sublanes
        scores = jnp.dot(k_ref[0, c], qt_ref[0, c],
                         preferred_element_type=f32,
                         precision=HIGHEST if k_ref.dtype == f32
                         else None)                          # [C, G C]
        for g in range(groups):
            at = slice(g * chunk, (g + 1) * chunk)
            a_ref[:, at] = (f32(scale) * scores[:, at]) ** 2 * \
                decay_ref[0, c]
        den_ref[0, c] = jnp.broadcast_to(
            jnp.sum(a_ref[...], axis=0, keepdims=True), (RUN_ALIGN, gc))
        num_ref[0, c] = jnp.dot(vt_ref[0, c].astype(f32), a_ref[...],
                                preferred_element_type=f32,
                                precision=HIGHEST)           # [d, G C]

        def one_window(w, _):
            def one_tile(t):
                tile = w * tiles + t
                i, j = run_ref[tile], col_ref[tile]
                at = pl.ds(pl.multiple_of(t * RUN_ALIGN, RUN_ALIGN),
                           RUN_ALIGN)
                cols = pl.ds(pl.multiple_of(j, RUN_ALIGN), RUN_ALIGN)
                # a run's first tile: j < i padding, j = i the square
                r = i - j
                mask = jnp.where(rowid < r, f32(0), jnp.where(
                    rowid == r, f32(np.sqrt(0.5)), f32(1)))
                phik_ref[at, :] = ku_ref[cols, :] * mask * \
                    kue_ref[pl.ds(i, 1), :]
                for g in range(groups):
                    lanes = slice(g * chunk, (g + 1) * chunk)
                    phiq_ref[at, lanes] = qu_ref[g, cols, :] * mask * \
                        que_ref[g, pl.ds(i, 1), :]

            # code size is set-up time in every process: a loop over
            # short straight runs, not one long one
            def some_tiles(u, _):
                for e in range(unroll):
                    one_tile(u * unroll + e)
                return 0
            jax.lax.fori_loop(0, tiles // unroll, some_tiles, 0)
            s0 = o_ref[0, 0, 0, rows(w), :].astype(f32)      # [W, d]
            num_ref[0, c] += jnp.dot(s0.T, phiq_ref[...],
                                     preferred_element_type=f32,
                                     precision=HIGHEST)
            o_ref[0, 0, 0, rows(w), :] = (carry * s0 + jnp.dot(
                phik_ref[...], v32_ref[...], preferred_element_type=f32,
                precision=HIGHEST)).astype(o_ref.dtype)
            return 0
        jax.lax.fori_loop(0, n_rows // window, one_window, 0)

    def chunks(c, carry):
        @pl.when(live_ref[c] != 0)
        def _():
            one_chunk(c)

        @pl.when(live_ref[c] == 0)
        def _():
            num_ref[0, c] = jnp.zeros(num_ref.shape[2:], f32)
            den_ref[0, c] = jnp.zeros(den_ref.shape[2:], f32)
        return carry
    jax.lax.fori_loop(0, live_ref.shape[0], chunks, 0)


def _launch(qt, k, kt, v, vt, decay, gates, S, li, slot, first, live,
            groups, scale, interpret):
    """The kernel on layer `li`, slot `slot` of S [L, slots, Hk, D, d]:
    (S with that slot advanced in place, num^T [Hk, n, d, G C], the
    chunks' own den [Hk, n, 8, G C])."""
    hk, n, d, gc = qt.shape
    chunk = gc // groups
    n_rows = S.shape[3]
    window = math.gcd(n_rows, _WINDOW)
    run, col = _tiles(d)
    head = lambda *shape: pl.BlockSpec(
        (1, n) + shape, lambda h, *_: (h, 0, 0, 0))
    state = pl.BlockSpec(
        (1, 1, 1, n_rows, d),
        lambda h, li_ref, slot_ref, *_: (li_ref[0], slot_ref[0], h, 0, 0))
    scalar = lambda x: jnp.reshape(x, (-1,)).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(hk,),
        in_specs=[head(d, gc), head(chunk, d), head(d, chunk),
                  head(chunk, d), head(d, chunk), head(chunk, chunk),
                  head(RUN_ALIGN, gates.shape[-1]), state],
        out_specs=[state, head(d, gc), head(RUN_ALIGN, gc)],
        scratch_shapes=[
            pltpu.VMEM((groups, d, chunk), f32),
            pltpu.VMEM((groups, d, chunk), f32),
            pltpu.VMEM((d, chunk), f32), pltpu.VMEM((d, chunk), f32),
            pltpu.VMEM((chunk, d), f32), pltpu.VMEM((chunk, gc), f32),
            pltpu.VMEM((window, gc), f32), pltpu.VMEM((window, chunk), f32)])
    # the scores and a V of the pairs; phi(q) S and phi(k) V of the state
    pairs = 2 * hk * n * chunk * chunk * d * 2 * groups
    through = 2 * hk * n * n_rows * d * chunk * (groups + 1)
    return pl.pallas_call(
        functools.partial(_kernel, d=d, groups=groups, chunk=chunk,
                          window=window, scale=scale),
        name="retention_prefill",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((hk, n, d, gc), f32),
                   jax.ShapeDtypeStruct((hk, n, RUN_ALIGN, gc), f32)],
        # operand 13 (after the six scalar operands and the seven
        # per-head operands) is the state: the output is the same buffer
        input_output_aliases={13: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=pairs + through,
            bytes_accessed=2 * hk * n_rows * d * S.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
    )(scalar(li), scalar(slot), scalar(first), scalar(live),
      jnp.asarray(run), jnp.asarray(col), qt, k, kt, v, vt, decay, gates, S)


def _square(z, d):
    """z [Hk, D] in the rows' order -> U [Hk, d, d], U[i, j] the row
    (i, j); zero where j < 8 (i // 8), which no row holds."""
    blocks, at = [], 0
    for b in range(0, d, RUN_ALIGN):
        size = RUN_ALIGN * (d - b)
        blocks.append(jnp.pad(
            z[:, at:at + size].reshape(-1, RUN_ALIGN, d - b),
            ((0, 0), (0, 0), (b, 0))))
        at += size
    return jnp.concatenate(blocks, axis=1)


def _rows(U):
    """`_square`'s inverse: U [Hk, d, d] -> [Hk, D]."""
    d = U.shape[-1]
    return jnp.concatenate(
        [U[:, b:b + RUN_ALIGN, b:].reshape(U.shape[0], -1)
         for b in range(0, d, RUN_ALIGN)], axis=1)


def retention_prefill_kernel(q, k, v, lg, S, z, li, slot, start, valid,
                             scale, eps, chunk):
    """`retention_prefill` through the kernel above: the pairs inside
    a chunk, the state's read-out and its update in it; the gates'
    sums, the normaliser and the divide in XLA. Mosaic on a TPU, the
    Pallas interpreter elsewhere."""
    _, t, hq, d = q.shape
    hk = k.shape[2]
    groups = hq // hk
    if d % RUN_ALIGN or S.shape[3] != state_dim(d, RUN_ALIGN):
        raise ValueError(
            f"a state of {S.shape[3]} rows at head width {d}: the kernel "
            f"takes widths that are multiples of {RUN_ALIGN}, their "
            f"runs aligned to {RUN_ALIGN} rows (`state_dim`)")
    n = -(-t // chunk)
    gc = groups * chunk

    def chunks(x):
        x = jnp.pad(x, ((0, n * chunk - t),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((n, chunk) + x.shape[1:])

    ok = chunks(valid)                                   # [n, C]
    qc = chunks(q[0].reshape(t, hk, groups, d))          # [n, C, Hk, G, d]
    kc, vc = chunks(k[0]), chunks(v[0])                  # [n, C, Hk, d]
    # the gates: a token's decay since the chunk began, to its end,
    # and the chunk's whole
    lgc = jnp.where(ok[..., None], chunks(lg[0].astype(f32)), f32(0))
    cum = jnp.cumsum(lgc, axis=1).transpose(2, 0, 1)     # [Hk, n, C]
    total = cum[..., -1:]
    e = jnp.exp(cum)
    left = jnp.where(ok[None], jnp.exp(total - cum), f32(0))
    carry = jnp.exp(total)                               # [Hk, n, 1]
    seen = (jnp.arange(chunk)[:, None] <= jnp.arange(chunk)[None, :]) & \
        ok[None, :, :, None]
    decay = jnp.exp(jnp.where(
        seen, cum[:, :, None, :] - cum[:, :, :, None], -jnp.inf))
    width = max(gc, d)
    row = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, width - x.shape[-1])))
    gates = jnp.stack(
        [row(jnp.tile(e, (1, 1, groups))), row(left),
         jnp.broadcast_to(carry, (hk, n, width))] +
        [jnp.zeros((hk, n, width), f32)] * (RUN_ALIGN - 3), axis=2)

    first = start == 0
    S, num, den = _launch(
        qc.transpose(2, 0, 4, 3, 1).reshape(hk, n, d, gc),
        kc.transpose(2, 0, 1, 3), kc.transpose(2, 0, 3, 1),
        vc.transpose(2, 0, 1, 3), vc.transpose(2, 0, 3, 1), decay, gates,
        S, li, slot, first, ok.any(axis=1), groups, scale,
        interpret=not decode._on_tpu())

    # the normaliser in its [d, d] form: U[i, j] the row (i, j)
    with jax.named_scope(SCOPE_STATE_RESET):
        z0 = jnp.where(first, f32(0), jax.lax.dynamic_slice(
            z, (li, slot, 0, 0), (1, 1) + z.shape[2:])[0, 0].astype(f32))
    upper = np.triu(np.full((d, d), np.sqrt(2.0)), 1) + np.eye(d)
    coeff = jnp.asarray(upper * scale, f32)
    k32 = kc.astype(f32)
    own = coeff * jnp.einsum("nchi,nchj,hnc->nhij", k32, k32, left,
                             precision=HIGHEST)

    def step(U, xs):
        own_c, carry_c = xs
        # kept in the state's type between chunks, as S is
        U1 = carry_c[:, None] * U + own_c
        return U1.astype(z.dtype).astype(f32), U
    U, before = jax.lax.scan(step, _square(z0, d),
                             (own, carry.transpose(1, 0, 2)))
    q32 = qc.astype(f32)
    den = den[:, :, 0].reshape(hk, n, groups, chunk).transpose(1, 3, 0, 2) \
        + e.transpose(1, 2, 0)[..., None] * jnp.einsum(
            "nchgi,nhij,nchgj->nchg", q32, coeff * before, q32,
            precision=HIGHEST)
    num = num.reshape(hk, n, d, groups, chunk).transpose(1, 4, 0, 3, 2)
    o = (num / (den[..., None] + f32(eps))).reshape(1, n * chunk, hq, d)
    z = jax.lax.dynamic_update_slice(
        z, _rows(U).astype(z.dtype)[None, None], (li, slot, 0, 0))
    return o[:, :t], S, z


def retention_prefill(q, k, v, lg, S, z, li, slot, start, valid, scale, eps,
                      chunk, chunked=retention_chunked):
    """`retention_chunked` for one slot's launch on layer `li`, slot
    `slot` of the whole state arrays S [L, slots, Hk, D, d] and z [L,
    slots, Hk, D] as they ride in the layer scan's carry. q [1, T, Hq,
    d]; k, v [1, T, Hk, d]; lg [1, T, Hk]; `valid` [T] marks the real
    tokens; a launch with `start == 0` starts from zero state whatever
    the slot holds. Returns (o [1, T, Hq, d] float32, S, z). Chosen by
    what is seen at trace time: the kernel where Mosaic takes it
    (`decode.usable`), else `retention_chunked` on the slot sliced out
    and set back (`chunked`: the caller's name for that form; the
    engine hands over its own, where the benchmark's tests put a
    faulty one)."""
    if decode.usable(S):
        return retention_prefill_kernel(q, k, v, lg, S, z, li, slot, start,
                                        valid, scale, eps, chunk)
    with jax.named_scope(SCOPE_STATE_RESET):
        zero = jnp.zeros((), S.dtype)
        S0 = jnp.where(start == 0, zero, jax.lax.dynamic_slice(
            S, (li, slot, 0, 0, 0), (1, 1) + S.shape[2:])[0])
        z0 = jnp.where(start == 0, zero, jax.lax.dynamic_slice(
            z, (li, slot, 0, 0), (1, 1) + z.shape[2:])[0])
    o, S1, z1 = chunked(q, k, v, lg, S0, z0, scale, eps, chunk, valid[None])
    return (o, jax.lax.dynamic_update_slice(S, S1[None], (li, slot, 0, 0, 0)),
            jax.lax.dynamic_update_slice(z, z1[None], (li, slot, 0, 0)))
