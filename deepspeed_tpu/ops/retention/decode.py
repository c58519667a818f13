"""Decode's step of gated power retention in one pass over the state
(Pallas TPU kernel): `retention_step`'s arithmetic for one token of
every slot, on one layer of the WHOLE state array as it rides in the
layer scan's carry,

    S : [n_layer, slots, Hk, D, d]      D = state_dim(d), d on the lanes

Per slot b and key/value head h the head's state S0 [D, d] is copied
HBM -> VMEM once, and on that one pass, float32 throughout,

    S1[(i,j), :] = g S0[(i,j), :] + c_ij s k_i k_j v[:]
    num[g', :]   = sum_(i,j) c_ij s q_i q_j S0[(i,j), :]     g' < G

S1 goes back to where S0 lay (`input_output_aliases`: nothing of the
array but layer `li` is touched, and nothing is copied). XLA's two
fusions read the state twice and wrote it once.

The per-row factors are scalars on the sublanes, and a [D, 1] operand
would be padded to the size of the state. The rows' order gives the
way round it (`retention.state_dim`): they come in runs of fixed i
with j ascending from 8 (i // 8), every run on an 8-row boundary. With
ku = sqrt(sqrt(2) s) k the factor of row (i, j), i < j, is ku_i ku_j,
so one [d, d] tile KV[j, :] = ku_j v[:] (and QT_g'[j, :] = qu_g',j on
every lane) serves every run: an 8-row tile of S0 in run i meets rows
j .. j + 7 of those tiles, times the run's one scalar ku_i (qu_g',i,
applied once a run to the run's sum). Only a run's first tile differs:
there rows j < i are padding and row j = i has c_ii = 1, which one of
eight constant masks (0 / 2^-1/2 / 1 by i mod 8) restores. About a
dozen vector operations a tile of state, against its copy in and out.

Slots with `fresh` start from zero state whatever they hold; slots
with `keep` (idle) have their state copied through unchanged: they are
read and written like the others, so the kernel's traffic does not
depend on what is live. The normaliser z (1/128 of the bytes) and the
token's own pair stay in XLA around the call.

Mosaic takes the kernel at d a multiple of 128 and a four-byte state;
the Pallas interpreter takes any d that is a multiple of 8, and a
state of any type (read as float32, written back in its own).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.retention.retention import (HIGHEST, LANE, RUN_ALIGN,
                                                   phi, read_out,
                                                   retention_step, state_dim)
from deepspeed_tpu.ops.transformer.flash_attention import _on_tpu

f32 = jnp.float32
# rows of `operands` before the query heads: ku, v, g
_HEAD_ROWS = 3
# both blocks of state, in and out, double-buffered (4 x 4.5 MB at
# d = 128), the tiles and room for the compiler
_VMEM_LIMIT = 48 * 1024 * 1024


def usable(S):
    """Whether Mosaic takes the kernel for this state [L, B, Hk, D, d]
    (module docstring); what it does not take runs as
    `retention_step`."""
    d = S.shape[-1]
    return _on_tpu() and d % LANE == 0 and S.dtype.itemsize == 4 and \
        S.shape[-2] == state_dim(d, RUN_ALIGN)


@functools.lru_cache(maxsize=None)
def _first_tile_masks(d):
    """[8, 8, d]: mask i mod 8 of a run's first tile, by row."""
    r = np.arange(RUN_ALIGN)
    m = np.where(r[None] < r[:, None], 0.0,
                 np.where(r[None] == r[:, None], np.sqrt(0.5), 1.0))
    return np.broadcast_to(m[:, :, None], (RUN_ALIGN, RUN_ALIGN, d)).astype(
        np.float32)


def _kernel(li_ref, keep_ref, fresh_ref, op_ref, mask_ref, s_ref, o_ref,
            num_ref, kt_ref, kv_ref, qt_ref, *, d, groups):
    b = pl.program_id(0)
    tiles = d // RUN_ALIGN
    rows8 = (RUN_ALIGN, d)
    n_rows = s_ref.shape[3]

    @pl.when(keep_ref[b] != 0)
    def _():
        def copy(t, carry):
            at = pl.ds(pl.multiple_of(t * RUN_ALIGN, RUN_ALIGN), RUN_ALIGN)
            o_ref[0, 0, 0, at, :] = s_ref[0, 0, 0, at, :]
            return carry
        jax.lax.fori_loop(0, n_rows // RUN_ALIGN, copy, 0)
        num_ref[...] = jnp.zeros(num_ref.shape, f32)

    @pl.when(keep_ref[b] == 0)
    def _():
        ops = op_ref[0, 0]                               # [R, d]
        square = lambda row: jnp.broadcast_to(row, (d, d)).T
        kt_ref[...] = square(ops[0:1])                   # [j, :] = ku_j
        kv_ref[...] = kt_ref[...] * ops[1:2]             # [j, :] = ku_j v
        for g in range(groups):
            qt_ref[g] = square(ops[_HEAD_ROWS + g:_HEAD_ROWS + g + 1])
        gate = jnp.broadcast_to(ops[2:3], rows8)
        fresh = fresh_ref[b] != 0
        zero = jnp.zeros(rows8, f32)

        def one_tile(base, block, t, ku_i, mask, sums):
            """Tile t (rows j = 8 t .. 8 t + 7) of the run whose rows
            start at `base` with its tile `block`; under `mask` if it
            is the run's first."""
            at = pl.ds(pl.multiple_of(
                base + (t - block) * RUN_ALIGN, RUN_ALIGN), RUN_ALIGN)
            tile = pl.ds(pl.multiple_of(t * RUN_ALIGN, RUN_ALIGN), RUN_ALIGN)
            s0 = s_ref[0, 0, 0, at, :].astype(f32)
            s0 = jnp.where(fresh, zero, s0)
            add = ku_i * kv_ref[tile, :]
            read = s0
            if mask is not None:
                add, read = add * mask, s0 * mask
            o_ref[0, 0, 0, at, :] = (gate * s0 + add).astype(o_ref.dtype)
            return tuple(sums[g] + qt_ref[g, tile, :] * read
                         for g in range(groups))

        # Run i = 8 block + r holds the tiles block .. tiles - 1. The
        # code is kept small (the program is lowered at every start of
        # a process, cached or not): blocks go by their remainder
        # modulo `unroll` (static: it fixes how many tiles stand
        # before whole groups of `unroll`), their quotient, the run in
        # the block and the groups of a run are loops.
        unroll = 4 if tiles % 4 == 0 else 1

        def runs_of(rest, quotient, acc):
            block = quotient * unroll + rest
            # rows before the block: 64 (tiles - b) for every b < block
            start = RUN_ALIGN * RUN_ALIGN * (
                block * tiles - jax.lax.div(block * (block - 1), 2))
            per_run = (tiles - block) * RUN_ALIGN
            odd = unroll - 1 - rest      # tiles between the first and
            #                              the whole groups

            def run(r, acc):
                i = block * RUN_ALIGN + r
                base = start + r * per_run
                ku_i = jnp.broadcast_to(kt_ref[pl.ds(i, 1), :], rows8)
                sums = one_tile(base, block, block, ku_i, mask_ref[r],
                                (zero,) * groups)
                for e in range(odd):
                    sums = one_tile(base, block, block + 1 + e, ku_i, None,
                                    sums)

                def group(c, sums):
                    for e in range(unroll):
                        sums = one_tile(
                            base, block, block + 1 + odd + c * unroll + e,
                            ku_i, None, sums)
                    return sums

                sums = jax.lax.fori_loop(0, tiles // unroll - 1 - quotient,
                                         group, sums)
                return tuple(
                    acc[g] + sums[g] * jnp.broadcast_to(
                        qt_ref[g, pl.ds(i, 1), :], rows8)
                    for g in range(groups))

            return jax.lax.fori_loop(0, RUN_ALIGN, run, acc)

        acc = (zero,) * groups
        for rest in range(unroll):
            acc = jax.lax.fori_loop(0, tiles // unroll,
                                    functools.partial(runs_of, rest), acc)
        num_ref[...] = jnp.zeros(num_ref.shape, f32)
        for g in range(groups):
            num_ref[0, 0, g:g + 1, :] = jnp.sum(acc[g], axis=0,
                                                keepdims=True)


def _advance(operands, S, li, keep, fresh, groups, interpret):
    """The kernel on layer `li` of S [L, B, Hk, D, d]: (S with that
    layer advanced in place, num [B, Hk, G, d])."""
    _, b, hk, n_rows, d = S.shape
    rows = operands.shape[2]
    padded = -(-groups // RUN_ALIGN) * RUN_ALIGN
    head = lambda s, h, *_: (s, h, 0, 0)
    state = lambda s, h, li_ref, *_: (li_ref[0], s, h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hk),
        in_specs=[pl.BlockSpec((1, 1, rows, d), head),
                  pl.BlockSpec((RUN_ALIGN, RUN_ALIGN, d),
                               lambda *_: (0, 0, 0)),
                  pl.BlockSpec((1, 1, 1, n_rows, d), state)],
        out_specs=[pl.BlockSpec((1, 1, 1, n_rows, d), state),
                   pl.BlockSpec((1, 1, padded, d), head)],
        scratch_shapes=[pltpu.VMEM((d, d), f32), pltpu.VMEM((d, d), f32),
                        pltpu.VMEM((groups, d, d), f32)])
    itemsize = S.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, d=d, groups=groups),
        name="retention_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((b, hk, padded, d), f32)],
        # operand 5 (after the three scalar operands, the per-head
        # operands and the masks) is the state: the output is the same
        # buffer
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * (1 + groups) * b * hk * n_rows * d,
            bytes_accessed=2 * b * hk * n_rows * d * itemsize,
            transcendentals=0),
        interpret=interpret,
    )(jnp.reshape(li, (1,)).astype(jnp.int32), keep.astype(jnp.int32),
      fresh.astype(jnp.int32), operands,
      jnp.asarray(_first_tile_masks(d)), S)


def retention_decode_kernel(q, k, v, lg, S, z, li, scale, eps, keep=None,
                            fresh=None):
    """`retention_decode` through the kernel above: the state's update
    and its read-out in it, the normaliser and the token's own pair in
    XLA. Mosaic on a TPU, the Pallas interpreter elsewhere."""
    b, hq, d = q.shape
    hk = k.shape[1]
    groups = hq // hk
    if d % RUN_ALIGN or S.shape[3] != state_dim(d, RUN_ALIGN):
        raise ValueError(
            f"a state of {S.shape[3]} rows at head width {d}: the kernel "
            f"takes widths that are multiples of {RUN_ALIGN}, their "
            f"runs aligned to {RUN_ALIGN} rows (`state_dim`)")
    keep = jnp.zeros((b,), bool) if keep is None else keep
    fresh = jnp.zeros((b,), bool) if fresh is None else fresh
    g = jnp.exp(lg.astype(f32))                          # [B, Hk]
    qg = q.reshape(b, hk, groups, d)
    root = f32(np.sqrt(np.sqrt(2.0) * scale))
    v32 = v.astype(f32)
    rows = -(-(_HEAD_ROWS + groups) // RUN_ALIGN) * RUN_ALIGN
    operands = jnp.concatenate([
        (root * k.astype(f32))[:, :, None], v32[:, :, None],
        jnp.broadcast_to(g[:, :, None, None], (b, hk, 1, d)),
        root * qg.astype(f32),
        jnp.zeros((b, hk, rows - _HEAD_ROWS - groups, d), f32)], axis=2)
    S, num = _advance(operands, S, li, keep, fresh, groups,
                      interpret=not _on_tpu())
    num = num[:, :, :groups]
    # the normaliser, 1/d of the state's bytes, and the token's own
    # pair
    z_l = z[li]
    z0 = jnp.where(fresh[:, None, None], f32(0), z_l.astype(f32))
    z1 = g[..., None] * z0 + phi(k, scale, z.shape[-1])
    den = jnp.einsum("bhgD,bhD->bhg", phi(qg, scale, z.shape[-1]), z0,
                     precision=HIGHEST)
    o = read_out(qg, k, v, g, num, den, scale, eps).reshape(b, hq, d)
    z1 = jnp.where(keep[:, None, None], z_l, z1.astype(z.dtype))
    return o, S, z.at[li].set(z1)


def retention_decode(q, k, v, lg, S, z, li, scale, eps, keep=None,
                     fresh=None):
    """`retention_step` on layer `li` of the whole state arrays S [L,
    B, Hk, D, d] and z [L, B, Hk, D] as they ride in the layer scan's
    carry (same operands and meaning otherwise; returns (o [B, Hq, d]
    float32, S, z)). Chosen by what is seen at trace time: the kernel
    where Mosaic takes it (`usable`), else `retention_step` on the
    layer sliced out and set back, which XLA does in place in three
    passes over the state."""
    if usable(S):
        return retention_decode_kernel(q, k, v, lg, S, z, li, scale, eps,
                                       keep=keep, fresh=fresh)
    o, S_l, z_l = retention_step(q, k, v, lg, S[li], z[li], scale, eps,
                                 keep=keep, fresh=fresh)
    return o, S.at[li].set(S_l), z.at[li].set(z_l)
