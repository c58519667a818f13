"""Fused MoE dispatch/combine: gather-scatter kernels over
capacity-indexed rows.

The PR-15 einsum pair materializes O(N*E*C) one-hot dispatch/combine
tensors and contracts them against the tokens — at, say,
N=2048, E=8, C=640 that is ~10M mask elements and ~E*C/k times more
FMAs than the k rows per token that actually move. This module is the
replacement: routing in INDEX form (`top_k_gating_indexed` —
e_idx/slot/keep/w, each [N, k]) drives

  * ``fused_dispatch(x, src)``  — [N, H] tokens -> [E*C, H]
    capacity-indexed rows: row s holds the token occupying slot s
    (zeros for empty slots). One gather per output row; `src` [E*C]
    maps slot -> token id with N as the empty-slot sentinel
    (`routing_slots` builds it from the routing dict).
  * ``fused_combine(ye_flat, dest, keep, w)`` — [E*C, H] expert
    outputs -> [N, H], each token summing its k slots scaled by the
    combine weight IN the kernel (fp32 accumulation). `dest` [N, k] is
    the slot index of choice j; dropped assignments contribute zero
    through keep.

Both carry a custom VJP shared by the two forward implementations —
the Pallas scalar-prefetch kernels (the block-sparse index-table
idiom: the slot map prefetches into SMEM and steers each grid step's
BlockSpec index_map) and the XLA take/segment-sum fallback — so
CPU CI, interpret mode and the TPU kernels differentiate identically:

  dispatch bwd: dx = segment_sum(d_xe by src)   (empty slots fall in
                the sentinel segment and are dropped);
  combine bwd:  d_ye = segment_sum(cw * dy by dest),
                d_cw[n, j] = <ye[dest[n, j]], dy[n]> — the gate-prob
                gradient path of the dense combine einsum, preserved.

Parity against the einsum pair (forward <= 5e-7 fp32, grads too) is
pinned in tests/test_overlap.py; its step time against the einsum
pair is not measured on the chip. The `moe_dispatch` autotune
family hashes THIS module's source for table invalidation.

Selection: `MoEConfig.fused_dispatch` ("auto"|"on"|"off") —
see moe/layer.py `resolve_fused_dispatch`. The fused path is local
gather/scatter math; expert-parallel meshes keep the GSPMD-declarative
einsum pair (its sharding constraints ARE the all-to-all), so "on" +
an expert-axis mesh is a config error, and "auto" only fuses on a
single TPU device: the kernels gather over the whole batch's slot
table, and GSPMD cannot partition a Mosaic call, so any sharded
program keeps the einsum pair.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _on_tpu():
    return jax.default_backend() == "tpu"


def _zeros_ct(x):
    """Zero cotangent matching x's tangent type (float0 for ints)."""
    dtype = np.result_type(getattr(x, "dtype", np.float32))
    # jax.dtypes, not np: numpy's issubdtype misclassifies bfloat16
    # (an ml_dtypes extension type) as non-inexact
    if jax.dtypes.issubdtype(dtype, np.inexact):
        return jnp.zeros(np.shape(x), dtype)
    return np.zeros(np.shape(x), jax.dtypes.float0)


def _resolve_ctx(use_pallas, interpret):
    """(impl, interpret) static context for the custom-VJP cores.
    use_pallas None = auto (Pallas on real TPU, XLA elsewhere); an
    explicit Pallas request off-TPU runs in interpret mode (there is
    no Mosaic lowering to fall back to on CPU)."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    interpret = bool(interpret) or (bool(use_pallas) and not _on_tpu())
    return ("pallas" if use_pallas else "xla", interpret)


def routing_slots(routing, num_experts, capacity):
    """Index-form routing -> the kernel's slot maps.

    Returns (src, dest): `src` [E*C] int32 maps slot -> occupying
    token id (N = empty-slot sentinel; slots are unique per assignment
    by the router's cumsum construction, so the scatter never
    collides); `dest` [N, k] int32 maps (token, choice) -> slot, always
    in range (dropped choices point at slot e_idx*C + 0 and are zeroed
    through keep). Both stop-gradiented — pure int plumbing."""
    e_idx, slot = routing["e_idx"], routing["slot"]
    keep = routing["keep"]
    n, k = e_idx.shape
    ec = int(num_experts) * int(capacity)
    dest = e_idx * jnp.int32(capacity) + slot                # [N, k]
    tok = jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
    # dropped assignments scatter out of bounds and are dropped
    scatter_idx = jnp.where(keep > 0, dest, jnp.int32(ec))
    src = jnp.full((ec,), n, jnp.int32)
    src = src.at[scatter_idx.reshape(-1)].set(
        tok.reshape(-1), mode="drop")
    return jax.lax.stop_gradient(src), jax.lax.stop_gradient(dest)


# ----------------------------------------------------------------------
# dispatch: [N, H] -> [E*C, H] row gather
# ----------------------------------------------------------------------
def _dispatch_kernel(src_ref, x_ref, o_ref):
    del src_ref  # consumed by the index_maps
    o_ref[...] = x_ref[...]


def _row_spec(h, index_map):
    """One [1, h] row of a [rows, 1, h] array per grid step. The
    leading dim is squeezed out of the block so its last two dims equal
    the array's: Mosaic refuses a (1, h) block over a [rows, h] array
    (second-minor block dim neither a multiple of 8 nor the whole
    dim)."""
    return pl.BlockSpec((None, 1, h), index_map)


def _dispatch_pallas(xp, src, interpret):
    s = src.shape[0]
    h = xp.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s,),
        in_specs=[_row_spec(h, lambda i, src_ref: (src_ref[i], 0, 0))],
        out_specs=_row_spec(h, lambda i, src_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _dispatch_kernel, name="moe_fused_dispatch",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, 1, h), xp.dtype),
        interpret=interpret)(src, xp[:, None, :])
    return out[:, 0, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch_core(ctx, x, src):
    impl, interpret = ctx
    # one zero row appended: the empty-slot sentinel gathers it, so no
    # in-kernel validity multiply is needed
    xp = jnp.concatenate(
        [x, jnp.zeros((1, x.shape[1]), x.dtype)], axis=0)
    if impl == "pallas":
        return _dispatch_pallas(xp, src, interpret)
    return jnp.take(xp, src, axis=0)


def _dispatch_core_fwd(ctx, x, src):
    # the empty (n, 0) array carries x's static shape/dtype through
    # the residuals (raw ints / np.dtype are not valid jax types)
    meta = jnp.zeros((x.shape[0], 0), x.dtype)
    return _dispatch_core(ctx, x, src), (src, meta)


def _dispatch_core_bwd(ctx, res, g):
    del ctx
    src, meta = res
    n = meta.shape[0]
    # empty slots land in the sentinel segment n and are discarded;
    # accumulate in at least fp32 (f64 inputs keep f64 — the parity
    # oracle path)
    acc = jnp.promote_types(meta.dtype, jnp.float32)
    dx = jax.ops.segment_sum(g.astype(acc), src,
                             num_segments=n + 1)[:n]
    return dx.astype(meta.dtype), _zeros_ct(src)


_dispatch_core.defvjp(_dispatch_core_fwd, _dispatch_core_bwd)


def fused_dispatch(x, src, use_pallas=None, interpret=False):
    """[N, H] tokens + slot map -> [E*C, H] capacity-indexed rows
    (reshape to [E, C, H] for the expert FFNs). Differentiable in x."""
    return _dispatch_core(_resolve_ctx(use_pallas, interpret), x, src)


# ----------------------------------------------------------------------
# combine: [E*C, H] -> [N, H] weighted k-row gather-sum
# ----------------------------------------------------------------------
def _make_combine_kernel(k, out_dtype):
    def kernel(dest_ref, cw_ref, *refs):
        del dest_ref  # consumed by the index_maps
        o_ref = refs[-1]
        base = pl.program_id(0) * k
        acc = refs[0][...].astype(jnp.float32) * cw_ref[base]
        for j in range(1, k):
            acc = acc + refs[j][...].astype(jnp.float32) * \
                cw_ref[base + j]
        o_ref[...] = acc.astype(out_dtype)
    return kernel


def _combine_pallas(ye_flat, dest, cw, interpret):
    n, k = dest.shape
    h = ye_flat.shape[1]

    def _ye_map(j):
        return lambda i, dest_ref, cw_ref: (dest_ref[i * k + j], 0, 0)

    # the slot and weight tables prefetch FLAT, like the block-sparse
    # index tables (SMEM is small; a 1-D table takes what it holds)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[_row_spec(h, _ye_map(j)) for j in range(k)],
        out_specs=_row_spec(h, lambda i, dest_ref, cw_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _make_combine_kernel(k, ye_flat.dtype), name="moe_fused_combine",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, h), ye_flat.dtype),
        interpret=interpret)(
            dest.reshape(-1), cw.reshape(-1),
            *([ye_flat[:, None, :]] * k))
    return out[:, 0, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine_core(ctx, ye_flat, dest, cw):
    impl, interpret = ctx
    if impl == "pallas":
        return _combine_pallas(ye_flat, dest, cw, interpret)
    acc = jnp.promote_types(ye_flat.dtype, jnp.float32)
    parts = jnp.take(ye_flat, dest, axis=0)          # [N, k, H]
    y = jnp.sum(cw[:, :, None].astype(acc) * parts.astype(acc),
                axis=1)
    return y.astype(ye_flat.dtype)


def _combine_core_fwd(ctx, ye_flat, dest, cw):
    return _combine_core(ctx, ye_flat, dest, cw), (ye_flat, dest, cw)


def _combine_core_bwd(ctx, res, dy):
    del ctx
    ye_flat, dest, cw = res
    s, h = ye_flat.shape
    n, k = dest.shape
    acc = jnp.promote_types(ye_flat.dtype, jnp.float32)
    dya = dy.astype(acc)
    contrib = (cw[:, :, None].astype(acc) *
               dya[:, None, :]).reshape(n * k, h)
    dye = jax.ops.segment_sum(contrib, dest.reshape(-1),
                              num_segments=s)
    parts = jnp.take(ye_flat, dest, axis=0).astype(acc)
    dcw = jnp.einsum("nkh,nh->nk", parts, dya)
    return (dye.astype(ye_flat.dtype), _zeros_ct(dest),
            dcw.astype(cw.dtype))


_combine_core.defvjp(_combine_core_fwd, _combine_core_bwd)


def fused_combine(ye_flat, dest, keep, w, use_pallas=None,
                  interpret=False):
    """[E*C, H] expert rows -> [N, H] combined tokens: token n sums
    its k slots scaled by keep * w (fp32 accumulation in-kernel).
    Differentiable in ye_flat and w (the gate-prob path); keep is the
    stop-gradiented capacity mask."""
    cw = (keep * w).astype(
        jnp.promote_types(w.dtype, jnp.float32))
    return _combine_core(_resolve_ctx(use_pallas, interpret),
                         ye_flat, dest, cw)
