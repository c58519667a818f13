"""Quantized-compute GEMMs: the per-block-scale machinery moved from
the wire into the matmul itself.

The repo already quantizes int8 with per-block scales in two places —
the ZeRO-Offload compressed wire (PR 1) and int8 weight-only serving
(PR 12, `inference/engine.py::quantize_param_tree`) — but until now the MXU never saw the
quantized values: quantization only compressed bytes in flight.  This
module is the ONE home of that scale layout and of the dequant
epilogues that consume it, shared by training and inference:

  scale layout (the PR-1 block machinery, per kernel [.., K, N]):
      weights:      one fp32 scale per (K-block, output-column)
                    -> scales [.., nb, N], nb = ceil(K / block)
      activations:  one fp32 scale per row (per token)
                    -> x_scales [.., rows, 1]

  epilogue families:
      * `int8_matmul`  — weight-only: x stays in the compute dtype,
        int8 weights are cast and contracted per K-block and the
        per-block scale multiplies each block's partial sum (the
        serving path: a model's block applies it to a leaf the
        engine's int8 load quantised).
      * `quantized_matmul` / `quantized_dense` — quantized compute:
        BOTH operands int8, the MXU contracts int8xint8 -> int32 and
        the dequant (x-row scale x weight-block scale) rides the GEMM
        epilogue.  On TPU this is a Pallas kernel (grid (M/bm, N/bn,
        nb), K innermost, fp32 accumulator scratch; int8 tiles obey
        the (32, 128) tiling floor so `block`/`block_n` must be
        128-multiples); elsewhere an XLA fallback reproduces the SAME
        quantization numerics with the dequantized operands feeding
        one fp32 GEMM (integer values ≤127 and block partial sums are
        exact in fp32, so fallback and kernel agree to fp32 roundoff).

Training (`quantized_dense`) wraps the forward in a straight-through
custom VJP: the forward runs the quantized GEMM off the CURRENT
weights (re-quantized every step inside the trace), the backward
treats quantization as identity — d x = g @ W_eff^T with
W_eff = dequant(quantize(W)) recomputed from the saved raw weights
(no extra residual memory), d W = x^T @ g in full precision.  The
backward GEMMs stay in the compute dtype: this is a *quantized
forward* matmul, the standard QAT contract.

`stochastic_rounding=True` rounds the int8 quantization stochastically
(floor(v + u), unbiased) when a `rng` is supplied — the engine threads
a per-step "quant" rng stream next to "dropout".  The same flag makes
the no-quantization bf16 fallback (`resolve_quantized_compute` ->
False with stochastic_rounding on) use an unbiased stochastically
rounded fp32->bf16 operand cast (`bf16_optimizer.stochastic_round_bf16`)
instead of truncation; without the flag that fallback is bit-for-bit
today's bf16 GEMM — backward compatible.

Parity is pinned by tests/test_quantized_matmul.py (GEMM error
against float32, and an engine's loss trajectory against bf16).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.per_device import ROWS, per_device

# default quantization block along the contraction dim for the
# quantized-compute (training) family. 128 = one MXU/lane tile, the
# Pallas kernel's minimum legal int8 K-tile. (Serving keeps its own
# 64 default — finer blocks, XLA epilogue only.)
DEFAULT_QUANT_BLOCK = 128

# key beside "kernel" in a projection's leaf dict that marks a kernel
# quantised once at load (weight-only serving): int8 values under
# "kernel", their [nb, N] scales under this; a block that finds it
# applies `int8_matmul` in place of the dense product
KERNEL_SCALE = "kernel_scale"

_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=64 * 1024 * 1024)


def _on_tpu():
    return jax.default_backend() == "tpu"


def resolve_quantized_compute(mode):
    """`quantized_compute` config value -> bool. "auto" enables the
    int8 compute path on real TPU only (the backend-keyed auto
    convention of fused_ops/head_packing: CPU numerics stay
    bit-identical by default); "on" forces it anywhere (XLA fallback
    off-TPU, same quantization numerics); "off" disables."""
    if mode in ("off", False, 0, None):
        return False
    if mode in ("on", True, 1):
        return True
    if mode == "auto":
        return _on_tpu()
    raise ValueError(
        f"quantized_compute={mode!r}: expected 'auto', 'on' or 'off'")


# ----------------------------------------------------------------------
# the shared scale layout: numpy (load-time, serving) + jnp (traced,
# training) quantizers. ONE formula: scale = maxabs/127 per
# (K-block, column), zero-scale blocks clamp to 1.
# ----------------------------------------------------------------------
def quantize_kernel_int8_np(w, block):
    """[.., K, N] fp kernel -> (q int8 [.., K, N], scales fp32
    [.., nb, N]) with K zero-padded conceptually to nb*block (scales
    for the pad region fall out of max-abs over the real rows).
    Numpy, for quantize-once-at-load users (the serving engine)."""
    w = np.asarray(w, np.float32)
    k = w.shape[-2]
    nb = -(-k // block)
    pad = nb * block - k
    if pad:
        wp = np.concatenate(
            [w, np.zeros(w.shape[:-2] + (pad, w.shape[-1]), np.float32)],
            axis=-2)
    else:
        wp = w
    blocks = wp.reshape(wp.shape[:-2] + (nb, block, wp.shape[-1]))
    s = (np.abs(blocks).max(axis=-2) / 127.0).astype(np.float32)
    safe = np.where(s > 0, s, 1.0).astype(np.float32)
    q = np.clip(np.rint(blocks / safe[..., None, :]), -127, 127)
    q = q.astype(np.int8).reshape(wp.shape)[..., :k, :]
    return q, s


def _round(v, rng):
    """Round-to-nearest, or unbiased stochastic floor(v + u) when a
    rng is supplied."""
    if rng is None:
        return jnp.rint(v)
    u = jax.random.uniform(rng, v.shape, jnp.float32)
    return jnp.floor(v + u)


def quantize_kernel_int8(w, block, rng=None, values_dtype=jnp.int8):
    """Traced twin of `quantize_kernel_int8_np`: [.., K, N] ->
    (q [.., nb*block, N] in `values_dtype`, scales fp32 [.., nb, N]).
    K is REALLY padded here (the consumer contracts over nb*block);
    pass values_dtype=float32 on the XLA fallback to skip the int8
    round trip (values are exact small integers either way)."""
    w = w.astype(jnp.float32)
    k = w.shape[-2]
    nb = -(-k // block)
    pad = nb * block - k
    if pad:
        w = jnp.concatenate(
            [w, jnp.zeros(w.shape[:-2] + (pad, w.shape[-1]),
                          jnp.float32)], axis=-2)
    blocks = w.reshape(w.shape[:-2] + (nb, block, w.shape[-1]))
    s = jnp.max(jnp.abs(blocks), axis=-2) / 127.0
    safe = jnp.where(s > 0, s, 1.0)
    q = jnp.clip(_round(blocks / safe[..., None, :], rng), -127, 127)
    q = q.astype(values_dtype).reshape(w.shape)
    return q, safe.astype(jnp.float32)


def quantize_rows_int8(x, rng=None, values_dtype=jnp.int8):
    """Per-row (per-token) activation quantization: [.., K] ->
    (q [.., K] in `values_dtype`, scales fp32 [.., 1])."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    safe = jnp.where(s > 0, s, 1.0)
    q = jnp.clip(_round(x / safe, rng), -127, 127)
    return q.astype(values_dtype), safe.astype(jnp.float32)


def dequantize_kernel(q, scales, block, k=None, dtype=jnp.float32):
    """(q [.., K', N], scales [.., nb, N]) -> dequantized [.., k, N]
    (k defaults to K' = whatever the quantizer produced)."""
    kp = q.shape[-2]
    nb = scales.shape[-2]
    pad = nb * block - kp
    if pad > 0:
        q = jnp.concatenate(
            [q, jnp.zeros(q.shape[:-2] + (pad, q.shape[-1]), q.dtype)],
            axis=-2)
    blocks = q.reshape(q.shape[:-2] + (nb, block, q.shape[-1]))
    deq = blocks.astype(jnp.float32) * scales[..., None, :]
    deq = deq.reshape(deq.shape[:-3] + (nb * block, deq.shape[-1]))
    return deq[..., :k if k is not None else kp, :].astype(dtype)


# ----------------------------------------------------------------------
# weight-only epilogue (the serving family)
# ----------------------------------------------------------------------
def int8_matmul(x, q, scales, block, out_dtype):
    """The weight-only dequant-in-matmul epilogue: x [.., T, K] @ int8
    q [K, N] with per-(block, column) scales [nb, N] -> [.., T, N] in
    out_dtype. Contraction runs per block in out_dtype with the scale
    applied to each block's partial sum — the int8 weights are never
    materialised in full precision."""
    k = x.shape[-1]
    nb = scales.shape[-2]
    pad = nb * block - k
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)
        q = jnp.concatenate(
            [q, jnp.zeros((pad, q.shape[-1]), q.dtype)], axis=0)
    xb = x.reshape(x.shape[:-1] + (nb, block)).astype(out_dtype)
    qb = q.reshape(nb, block, q.shape[-1]).astype(out_dtype)
    part = jnp.einsum("...bk,bkn->...bn", xb, qb)
    return (part * scales.astype(out_dtype)).sum(axis=-2)


# ----------------------------------------------------------------------
# quantized-compute GEMM: int8 x int8 with the dequant in the epilogue
# ----------------------------------------------------------------------
def _qmm_kernel(xq_ref, wq_ref, sx_ref, sw_ref, out_ref, acc_scr, *,
                nb, out_dtype):
    """One (bm, bn) output tile, K innermost: int8 tiles contract on
    the MXU into int32, each K-block's partial is scaled by its weight
    block-column scale into the fp32 accumulator, and the epilogue
    applies the per-row activation scale on the single output write."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    part = jax.lax.dot_general(
        xq_ref[...], wq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    acc_scr[...] += part.astype(jnp.float32) * sw_ref[...]

    @pl.when(k == nb - 1)
    def _():
        out_ref[...] = (acc_scr[...] * sx_ref[...]).astype(out_dtype)


def _qmm_pallas(xq, wq, sx, sw, block, out_dtype, block_m, block_n,
                interpret):
    """[M, Kp] int8 @ [Kp, N] int8 via the Pallas epilogue kernel.
    Kp = nb*block (pre-padded by the quantizers); M/N pad here. On a
    mesh each device multiplies its own rows by the whole weight."""
    launch = functools.partial(
        _qmm_launch, block=block, out_dtype=out_dtype, block_m=block_m,
        block_n=block_n, interpret=interpret)
    out, = per_device(
        launch, in_dims=((ROWS, None), (None, None), (ROWS, None),
                         (None, None)),
        out_dims=((ROWS, None),))(xq, wq, sx, sw)
    return out


def _qmm_launch(xq, wq, sx, sw, *, block, out_dtype, block_m, block_n,
                interpret):
    m, kp = xq.shape
    n = wq.shape[-1]
    nb = kp // block
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    if mp != m:
        xq = jnp.pad(xq, ((0, mp - m), (0, 0)))
        sx = jnp.pad(sx, ((0, mp - m), (0, 0)), constant_values=1.0)
    if np_ != n:
        wq = jnp.pad(wq, ((0, 0), (0, np_ - n)))
        sw = jnp.pad(sw, ((0, 0), (0, np_ - n)), constant_values=1.0)
    # sw rides as [nb, 1, N] with the K-block dim squeezed out of the
    # block, so the block's last two dims are (1, block_n) over an
    # array whose matching dims are (1, N): Mosaic requires the
    # second-minor block dim to be a multiple of 8 or the whole dim
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, nb=nb, out_dtype=out_dtype),
        name="quantized_matmul",
        grid=(mp // block_m, np_ // block_n, nb),
        in_specs=[
            pl.BlockSpec((block_m, block), lambda i, j, k: (i, k)),
            pl.BlockSpec((block, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((None, 1, block_n), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret)(xq, wq, sx, sw[:, None, :])
    return (out[:m, :n],)


def _resolve_impl(impl):
    """impl -> (use_pallas, interpret)."""
    if impl in ("auto", None):
        return (True, False) if _on_tpu() else (False, False)
    if impl == "pallas":
        return True, False
    if impl == "interpret":
        return True, True
    if impl == "xla":
        return False, False
    raise ValueError(
        f"impl={impl!r}: expected 'auto', 'pallas', 'xla' or "
        "'interpret'")


def _qmm_blocks(m, k, n, dtype, block_m, block_n):
    """Tile sizes: explicit args win, then the autotune table, then
    the hand-picked 256/256."""
    if block_m is not None and block_n is not None:
        return int(block_m), int(block_n)
    from deepspeed_tpu.ops import autotune
    tuned = autotune.qmm_blocks(m, k, n, dtype)
    if tuned is not None:
        return tuned
    return 256, 256


def quantized_matmul(x, wq, sw, *, block, out_dtype=None, x_rng=None,
                     impl="auto", block_m=None, block_n=None):
    """x [.., K] (any float dtype) @ PRE-quantized weights
    (wq [nb*block or K, N] int8-valued, sw [nb, N]) -> [.., N].

    Quantizes the activations per row on the fly (stochastically when
    x_rng is given) and runs the int8xint8 dequant-epilogue GEMM: the
    Pallas kernel on TPU (block_m/block_n from the autotune table
    unless passed), the exact-integer fp32 fallback elsewhere. This is
    the forward core `quantized_dense` differentiates through."""
    out_dtype = np.dtype(out_dtype) if out_dtype is not None \
        else x.dtype
    use_pallas, interpret = _resolve_impl(impl)
    k = x.shape[-1]
    n = wq.shape[-1]
    nb = sw.shape[-2]
    kp = nb * block
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    with jax.named_scope("quantized_matmul"):
        vdt = jnp.int8 if use_pallas else jnp.float32
        xq, sx = quantize_rows_int8(x.reshape(m, k), rng=x_rng,
                                    values_dtype=vdt)
        if kp != k:
            xq = jnp.pad(xq, ((0, 0), (0, kp - k)))
        if wq.shape[-2] != kp:
            wq = jnp.pad(wq, ((0, kp - wq.shape[-2]), (0, 0)))
        if use_pallas:
            bm, bn = _qmm_blocks(m, k, n, out_dtype, block_m, block_n)
            out = _qmm_pallas(xq.astype(jnp.int8),
                              wq.astype(jnp.int8), sx, sw, block,
                              out_dtype, bm, bn, interpret)
        else:
            # fallback: dequantized operands, ONE fp32 GEMM. Integer
            # values <= 127 and their block sums are exact in fp32, so
            # this reproduces the kernel's numerics to fp32 roundoff.
            wd = dequantize_kernel(wq, sw, block)
            out = ((xq.astype(jnp.float32) @ wd) * sx).astype(out_dtype)
        return out.reshape(lead + (n,))


def _zeros_ct(x):
    """Zero cotangent matching x's tangent type (float0 for ints/keys,
    zeros for inexact) — the stage3 `_zeros_ct` convention for inputs
    whose gradient is discarded by construction (the rng)."""
    from jax import dtypes
    dtype = np.result_type(getattr(x, "dtype", np.float32))
    if np.issubdtype(dtype, np.inexact):
        return jnp.zeros(np.shape(x), dtype)
    return np.zeros(np.shape(x), dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _qdense(x, w, rng, block, out_dtype, sr, impl):
    wq, sw = quantize_kernel_int8(
        w, block, rng=rng if sr else None,
        values_dtype=jnp.int8 if _resolve_impl(impl)[0]
        else jnp.float32)
    return quantized_matmul(
        x, wq, sw, block=block, out_dtype=out_dtype,
        x_rng=jax.random.fold_in(rng, 1) if sr else None, impl=impl)


def _qdense_fwd(x, w, rng, block, out_dtype, sr, impl):
    # residuals are the RAW operands (aliased, no extra memory); the
    # backward re-derives W_eff by re-quantizing deterministically
    return _qdense(x, w, rng, block, out_dtype, sr, impl), (x, w, rng)


def _qdense_bwd(block, out_dtype, sr, impl, res, g):
    x, w, rng = res
    # straight-through: forward y = x_q @ W_eff; backward treats both
    # quantizations as identity around the dequantized weights
    wq, sw = quantize_kernel_int8(w, block,
                                  rng=rng if sr else None,
                                  values_dtype=jnp.float32)
    w_eff = dequantize_kernel(wq, sw, block, k=w.shape[-2],
                              dtype=x.dtype)
    gc = g.astype(x.dtype)
    dx = jnp.einsum("...n,kn->...k", gc, w_eff)
    dw = jnp.einsum("...k,...n->kn", x.astype(jnp.float32),
                    g.astype(jnp.float32)).astype(w.dtype)
    return dx.astype(x.dtype), dw, _zeros_ct(rng)


_qdense.defvjp(_qdense_fwd, _qdense_bwd)


def quantized_dense(x, kernel, *, block=DEFAULT_QUANT_BLOCK,
                    out_dtype=None, stochastic_rounding=False,
                    rng=None, impl="auto"):
    """y = x @ kernel with the int8 quantized-compute forward and a
    straight-through backward — the training entry point (the third
    fused-ops epilogue family).

    kernel [K, N] is quantized per-(K-block, N-column) INSIDE the
    trace (fresh every step — the weights move); x quantizes per row.
    `block` must be a multiple of 128 on the Pallas path (int8 lane
    tiling); any positive block works on the XLA fallback.
    stochastic_rounding rounds both quantizations stochastically when
    `rng` is provided (the engine's per-step "quant" stream); without
    a rng it falls back to round-to-nearest."""
    if block <= 0:
        raise ValueError(f"quantized_compute block must be > 0, "
                         f"got {block}")
    use_pallas, _ = _resolve_impl(impl)
    if use_pallas and block % 128:
        raise ValueError(
            f"quantized_compute block must be a multiple of 128 on "
            f"the Pallas path (int8 lane tiling), got {block}; use "
            f"impl='xla' for finer blocks")
    out_dtype = np.dtype(out_dtype) if out_dtype is not None \
        else x.dtype
    sr = bool(stochastic_rounding) and rng is not None
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return _qdense(x, kernel, rng, int(block), out_dtype, sr, impl)


def bf16_fallback_matmul(x, kernel, *, out_dtype=None,
                         stochastic_rounding=False, rng=None):
    """The backward-compatible fallback when quantized compute
    resolves OFF: a plain compute-dtype GEMM, bit-for-bit today's
    path — unless stochastic_rounding is on AND a rng is supplied, in
    which case the fp32->bf16 operand casts round stochastically
    (unbiased) instead of truncating."""
    out_dtype = np.dtype(out_dtype) if out_dtype is not None \
        else x.dtype
    if stochastic_rounding and rng is not None and \
            out_dtype == np.dtype(jnp.bfloat16):
        from deepspeed_tpu.runtime.bf16_optimizer import \
            stochastic_round_bf16
        r1, r2 = jax.random.split(rng)
        x = stochastic_round_bf16(x.astype(jnp.float32), r1)
        kernel = stochastic_round_bf16(kernel.astype(jnp.float32), r2)
    y = jax.lax.dot_general(
        x.astype(out_dtype), kernel.astype(out_dtype),
        (((x.ndim - 1,), (0,)), ((), ())))
    return y
