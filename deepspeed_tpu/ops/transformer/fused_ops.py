"""Fused non-attention epilogue kernels: the transformer hot loop's
elementwise chains as single Pallas launches.

TPU-native rebuild of the reference's fused transformer kernel scope
(`csrc/transformer/ds_transformer_cuda.cpp` — `launch_bias_add`,
`launch_bias_gelu`, `launch_fused_add2` + `normalize_kernels.cu`): the
two elementwise chains that stand between the matmuls of the
GPT-2/BERT block (the reference fuses the same two) are

  (a) bias + residual-add + LayerNorm   (the block epilogue)
  (b) bias + GeLU                       (the MLP activation; exact-erf
                                         form per the reference kernel,
                                         plus the tanh approximation
                                         GPT-2 uses)

XLA compiles each chain into several fusions with HBM-materialized
intermediates (the LayerNorm reductions split the fusion); the Pallas
forward kernel streams one row block through VMEM and writes exactly
two tensors — the normalized output and the residual sum.  The custom
VJP runs a single backward kernel per chain (dX / d_bias / d_gamma /
d_beta in one pass, cross-block accumulators in VMEM scratch) instead
of XLA's autodiff chain.

Remat contract (the per-fusion policy, mirroring the
`_flash_apply` split in flash_attention.py): the forward kernel runs on
`stop_gradient` inputs and its outputs carry `checkpoint_name`
annotations —

    "fused_ln_out"    LN output           (feeds the next matmul)
    "fused_ln_sum"    bias+residual sum   (the residual stream AND the
                                           only backward residual)
    "fused_gelu_sum"  bias+input sum      (the only GeLU bwd residual)
    "fused_gelu_out"  GeLU output

so the `save_fused_epilogues` policy
(runtime/activation_checkpointing/checkpointing.py) saves the kernels'
outputs and the rematted backward never re-runs a fused forward: every
backward residual is either a saved named output or recomputed from one
with cheap reductions (mu/rstd from the saved sum).  The GeLU OUTPUT is
deliberately NOT in the policy (it is `4·H` wide — the roofline's
bytes verdict; it recomputes from the saved sum with one transcendental
pass).

`impl="auto"` lowers to the Pallas kernels on real TPU and to a fused
jnp formulation (same custom VJP, same saved set) elsewhere —
CPU CI validates the kernel logic itself via `impl="interpret"`.
Every entry point runs inside a `jax.named_scope` carrying the op name,
which is what a device trace's rows are attributed by (the names
`benchmark/scope_reduce.py` sums a traced run's device time under).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.per_device import COLS, ROWS, per_device

# names the save_fused_epilogues remat policy saves (fused_gelu_out is
# named but EXCLUDED from the policy: 4·H bytes/token vs a one-erf
# recompute from the saved sum)
FUSED_LN_OUT = "fused_ln_out"
FUSED_LN_SUM = "fused_ln_sum"
FUSED_GELU_SUM = "fused_gelu_sum"
FUSED_GELU_OUT = "fused_gelu_out"
FUSED_EPILOGUE_SAVE_NAMES = (FUSED_LN_OUT, FUSED_LN_SUM, FUSED_GELU_SUM)

_SQRT_2 = 1.4142135623730951
_SQRT_2_OVER_PI = 0.7978845608028654   # sqrt(2/pi), the tanh-gelu const
_INV_SQRT_2PI = 0.3989422804014327     # 1/sqrt(2*pi)
_GELU_C = 0.044715

_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=64 * 1024 * 1024)


def _on_tpu():
    return jax.default_backend() == "tpu"


def resolve_fused_ops(mode, dropout_inactive=True):
    """`fused_ops` config value -> bool.  "auto" enables the fused path
    on real TPU when dropout does not sit inside the chain (dropout
    between the bias add and the residual would change semantics) — the
    same backend-keyed auto convention as `head_packing` and
    `mlm_head_in_compute_dtype`, so CPU numerics stay bit-identical by
    default.  "on" forces it on any backend (XLA-fallback off-TPU) and
    refuses dropout loudly; "off" disables."""
    if mode in ("off", False, 0, None):
        return False
    if mode in ("on", True, 1):
        if not dropout_inactive:
            raise ValueError(
                "fused_ops='on' requires inactive dropout (deterministic "
                "or rate 0): dropout sits between the bias add and the "
                "residual, which the fused chain cannot express; use "
                "'auto' to fall back automatically")
        return True
    if mode == "auto":
        return bool(dropout_inactive) and _on_tpu()
    raise ValueError(
        f"fused_ops={mode!r}: expected 'auto', 'on' or 'off'")


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
        f"impl={impl!r}: expected 'auto', 'pallas', 'xla' or 'interpret'")


def _row_block(n, target=256):
    """Largest power-of-two row-block <= target dividing n (floor 1)."""
    blk = min(target, n)
    while blk > 1 and n % blk:
        blk //= 2
    return max(blk, 1)


_DEFAULT_ROW_BLOCK = 256


def _tuned_row_block(kernel, n, hp, dtype):
    """Row-block for one launch: the autotune table's winner for this
    (kernel, backend, dtype, shape-class) when one exists, else the
    hand-picked 256 target. Pure host-side dict lookup at trace time
    (no device sync)."""
    from deepspeed_tpu.ops import autotune
    target = autotune.row_block_target(kernel, n, hp, dtype)
    return _row_block(n, target or _DEFAULT_ROW_BLOCK)


# ----------------------------------------------------------------------
# shared math (the kernels and the XLA fallback use the SAME formulas,
# so interpret-mode parity tests pin the kernel logic itself)
# ----------------------------------------------------------------------
def _ln_stats(s, h_valid, h_padded):
    """fp32 row mean / rstd over the last axis, masking pad lanes when
    the wrapper padded H up to a lane multiple.  Mirrors flax
    LayerNorm's fast-variance formula (E[x^2] - E[x]^2, clamped)."""
    if h_valid == h_padded:
        mu = jnp.mean(s, axis=-1, keepdims=True)
        mu2 = jnp.mean(s * s, axis=-1, keepdims=True)
    else:
        mu = jnp.sum(s, axis=-1, keepdims=True) / h_valid
        mu2 = jnp.sum(s * s, axis=-1, keepdims=True) / h_valid
    var = jnp.maximum(mu2 - mu * mu, 0.0)
    return mu, var


def _ln_fwd_math(y, bias, residual, gamma, beta, eps, h_valid):
    """fp32 chain: s = (y + bias) + residual; out = LN(s)*gamma+beta."""
    s = (y.astype(jnp.float32) + bias.astype(jnp.float32)) + \
        residual.astype(jnp.float32)
    h_padded = s.shape[-1]
    if h_valid != h_padded:
        lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
        s = jnp.where(lane < h_valid, s, 0.0)
    mu, var = _ln_stats(s, h_valid, h_padded)
    rstd = jax.lax.rsqrt(var + eps)
    out = (s - mu) * rstd * gamma.astype(jnp.float32) + \
        beta.astype(jnp.float32)
    if h_valid != h_padded:
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, out.ndim - 1)
        out = jnp.where(lane < h_valid, out, 0.0)
    return out, s


def _ln_bwd_math(s, gamma, d_out, d_sum, eps, h_valid):
    """One-pass LN backward off the saved sum `s` (mu/rstd recomputed —
    cheap reductions instead of saved tensors).  Returns
    (ds_total, d_gamma_rows, d_beta_rows) where ds_total is the shared
    cotangent of y, bias (row-summed by the caller) and residual."""
    s = s.astype(jnp.float32)
    d_out = d_out.astype(jnp.float32)
    h_padded = s.shape[-1]
    if h_valid != h_padded:
        lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
        valid = lane < h_valid
        s = jnp.where(valid, s, 0.0)
        d_out = jnp.where(valid, d_out, 0.0)
    mu, var = _ln_stats(s, h_valid, h_padded)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (s - mu) * rstd
    dxhat = d_out * gamma.astype(jnp.float32)
    if h_valid != h_padded:
        dxhat = jnp.where(valid, dxhat, 0.0)
        mean_dxhat = jnp.sum(dxhat, -1, keepdims=True) / h_valid
        mean_dxhat_x = jnp.sum(dxhat * xhat, -1, keepdims=True) / h_valid
    else:
        mean_dxhat = jnp.mean(dxhat, -1, keepdims=True)
        mean_dxhat_x = jnp.mean(dxhat * xhat, -1, keepdims=True)
    ds = rstd * (dxhat - mean_dxhat - xhat * mean_dxhat_x)
    if d_sum is not None:
        ds = ds + d_sum.astype(jnp.float32)
    if h_valid != h_padded:
        ds = jnp.where(valid, ds, 0.0)
    d_gamma_rows = d_out * xhat
    return ds, d_gamma_rows, d_out


def _gelu_fwd_math(x, bias, approximate):
    """fp32 s = x + bias; out = gelu(s) (erf exact or tanh approx —
    same formulas as jax.nn.gelu, so unfused parity is roundoff)."""
    s = x.astype(jnp.float32) + bias.astype(jnp.float32)
    # association order mirrors jax.nn.gelu exactly (s * cdf), so the
    # fused/unfused fp32 forward is bit-identical
    if approximate:
        cdf = 0.5 * (1.0 + jnp.tanh(_SQRT_2_OVER_PI *
                                    (s + _GELU_C * (s ** 3))))
        out = s * cdf
    else:
        out = s * (jax.lax.erf(s / _SQRT_2) + 1.0) / 2.0
    return out, s


def _gelu_bwd_math(s, d_out, approximate):
    """d gelu(s)/ds * d_out off the saved sum."""
    s = s.astype(jnp.float32)
    d_out = d_out.astype(jnp.float32)
    if approximate:
        inner = _SQRT_2_OVER_PI * (s + _GELU_C * s * s * s)
        t = jnp.tanh(inner)
        dinner = _SQRT_2_OVER_PI * (1.0 + 3.0 * _GELU_C * s * s)
        grad = 0.5 * (1.0 + t) + 0.5 * s * (1.0 - t * t) * dinner
    else:
        grad = 0.5 * (1.0 + jax.lax.erf(s / _SQRT_2)) + \
            s * jnp.exp(-0.5 * s * s) * _INV_SQRT_2PI
    return d_out * grad


# ----------------------------------------------------------------------
# Pallas kernels — one row block per grid step, H on the lanes
# ----------------------------------------------------------------------
def _ln_fwd_kernel(y_ref, bias_ref, res_ref, gamma_ref, beta_ref,
                   out_ref, sum_ref, *, eps, h_valid):
    out, s = _ln_fwd_math(y_ref[...], bias_ref[...], res_ref[...],
                          gamma_ref[...], beta_ref[...], eps, h_valid)
    out_ref[...] = out.astype(out_ref.dtype)
    sum_ref[...] = s.astype(sum_ref.dtype)


def _ln_bwd_kernel(s_ref, gamma_ref, dout_ref, dsum_ref, dx_ref,
                   dbias_ref, dgamma_ref, dbeta_ref,
                   db_scr, dg_scr, dbeta_scr, *, eps, h_valid,
                   has_dsum):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _():
        db_scr[...] = jnp.zeros_like(db_scr)
        dg_scr[...] = jnp.zeros_like(dg_scr)
        dbeta_scr[...] = jnp.zeros_like(dbeta_scr)

    dsum = dsum_ref[...] if has_dsum else None
    ds, dg_rows, dbeta_rows = _ln_bwd_math(
        s_ref[...], gamma_ref[...], dout_ref[...], dsum, eps, h_valid)
    dx_ref[...] = ds.astype(dx_ref.dtype)
    db_scr[...] += jnp.sum(ds, axis=0, keepdims=True)
    dg_scr[...] += jnp.sum(dg_rows, axis=0, keepdims=True)
    dbeta_scr[...] += jnp.sum(dbeta_rows, axis=0, keepdims=True)

    @pl.when(i == n - 1)
    def _():
        dbias_ref[...] = db_scr[...].astype(dbias_ref.dtype)
        dgamma_ref[...] = dg_scr[...].astype(dgamma_ref.dtype)
        dbeta_ref[...] = dbeta_scr[...].astype(dbeta_ref.dtype)


def _gelu_fwd_kernel(x_ref, bias_ref, out_ref, sum_ref, *, approximate):
    out, s = _gelu_fwd_math(x_ref[...], bias_ref[...], approximate)
    out_ref[...] = out.astype(out_ref.dtype)
    sum_ref[...] = s.astype(sum_ref.dtype)


def _gelu_bwd_kernel(s_ref, dout_ref, dx_ref, dbias_ref, db_scr, *,
                     approximate):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _():
        db_scr[...] = jnp.zeros_like(db_scr)

    dx = _gelu_bwd_math(s_ref[...], dout_ref[...], approximate)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    db_scr[...] += jnp.sum(dx, axis=0, keepdims=True)

    @pl.when(i == n - 1)
    def _():
        dbias_ref[...] = db_scr[...].astype(dbias_ref.dtype)


def _pad_lanes(x, h_padded):
    h = x.shape[-1]
    if h == h_padded:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, h_padded - h)])


def _pallas_call(kernel, **kwargs):
    return pl.pallas_call(kernel, compiler_params=_COMPILER_PARAMS,
                          **kwargs)


# The launchers below take [N, H] row-flattened operands. On a mesh
# each device launches on its own rows (per_device): the LayerNorm
# width is held whole, the GeLU width may be a tensor-parallel column
# block. Parameter gradients leave the kernels in fp32 — they are sums
# over rows, added over the devices that divided the rows before the
# caller rounds them to the parameter dtype.
_ROW_BLOCK = (ROWS, None)
_VEC = (None,)


def _ln_fwd_launch(y2, bias, res2, gamma, beta, eps, h, out_dtype,
                   sum_dtype, interpret):
    """Pads H to a lane multiple (the kernel masks pad lanes out of the
    statistics) and tiles rows."""
    def launch(y2, bias, res2, gamma, beta):
        n = y2.shape[0]
        hp = -(-h // 128) * 128
        blk = _tuned_row_block("fused_ln", n, hp, out_dtype)
        args = [_pad_lanes(y2, hp), _pad_lanes(bias[None], hp),
                _pad_lanes(res2, hp), _pad_lanes(gamma[None], hp),
                _pad_lanes(beta[None], hp)]
        row_spec = pl.BlockSpec((blk, hp), lambda i: (i, 0))
        vec_spec = pl.BlockSpec((1, hp), lambda i: (0, 0))
        out, s = _pallas_call(
            functools.partial(_ln_fwd_kernel, eps=eps, h_valid=h),
            grid=(n // blk,),
            in_specs=[row_spec, vec_spec, row_spec, vec_spec, vec_spec],
            out_specs=[row_spec, row_spec],
            out_shape=[jax.ShapeDtypeStruct((n, hp), out_dtype),
                       jax.ShapeDtypeStruct((n, hp), sum_dtype)],
            scratch_shapes=[], interpret=interpret,
            name="fused_bias_residual_layernorm_fwd")(*args)
        return out[:, :h], s[:, :h]
    return per_device(
        launch, in_dims=(_ROW_BLOCK, _VEC, _ROW_BLOCK, _VEC, _VEC),
        out_dims=(_ROW_BLOCK, _ROW_BLOCK))(y2, bias, res2, gamma, beta)


def _ln_bwd_launch(s2, gamma, dout2, dsum2, eps, h, in_dtype, interpret):
    has_dsum = dsum2 is not None

    def launch(s2, gamma, dout2, *dsum2):
        n = s2.shape[0]
        hp = -(-h // 128) * 128
        blk = _tuned_row_block("fused_ln", n, hp, in_dtype)
        args = [_pad_lanes(s2, hp), _pad_lanes(gamma[None], hp),
                _pad_lanes(dout2, hp)]
        row_spec = pl.BlockSpec((blk, hp), lambda i: (i, 0))
        vec_spec = pl.BlockSpec((1, hp), lambda i: (0, 0))
        in_specs = [row_spec, vec_spec, row_spec]
        if has_dsum:
            args.append(_pad_lanes(dsum2[0], hp))
            in_specs.append(row_spec)
        else:
            args.append(jnp.zeros((1, hp), jnp.float32))
            in_specs.append(vec_spec)
        dx, dbias, dgamma, dbeta = _pallas_call(
            functools.partial(_ln_bwd_kernel, eps=eps, h_valid=h,
                              has_dsum=has_dsum),
            grid=(n // blk,),
            in_specs=in_specs,
            out_specs=[row_spec, vec_spec, vec_spec, vec_spec],
            out_shape=[jax.ShapeDtypeStruct((n, hp), in_dtype)] +
            [jax.ShapeDtypeStruct((1, hp), jnp.float32)] * 3,
            scratch_shapes=[pltpu.VMEM((1, hp), jnp.float32)] * 3,
            interpret=interpret,
            name="fused_bias_residual_layernorm_bwd")(*args)
        return dx[:, :h], dbias[0, :h], dgamma[0, :h], dbeta[0, :h]
    return per_device(
        launch,
        in_dims=(_ROW_BLOCK, _VEC, _ROW_BLOCK) +
        ((_ROW_BLOCK,) if has_dsum else ()),
        out_dims=(_ROW_BLOCK, _VEC, _VEC, _VEC), row_summed=(1, 2, 3))(
            s2, gamma, dout2, *((dsum2,) if has_dsum else ()))


_COL_BLOCK = (ROWS, COLS)
_COL_VEC = (COLS,)


def _gelu_fwd_launch(x2, bias, approximate, out_dtype, sum_dtype,
                     interpret):
    def launch(x2, bias):
        n, h = x2.shape
        hp = -(-h // 128) * 128
        blk = _tuned_row_block("fused_gelu", n, hp, out_dtype)
        row_spec = pl.BlockSpec((blk, hp), lambda i: (i, 0))
        vec_spec = pl.BlockSpec((1, hp), lambda i: (0, 0))
        out, s = _pallas_call(
            functools.partial(_gelu_fwd_kernel, approximate=approximate),
            grid=(n // blk,),
            in_specs=[row_spec, vec_spec],
            out_specs=[row_spec, row_spec],
            out_shape=[jax.ShapeDtypeStruct((n, hp), out_dtype),
                       jax.ShapeDtypeStruct((n, hp), sum_dtype)],
            scratch_shapes=[], interpret=interpret,
            name="fused_bias_gelu_fwd")(
                _pad_lanes(x2, hp), _pad_lanes(bias[None], hp))
        return out[:, :h], s[:, :h]
    return per_device(launch, in_dims=(_COL_BLOCK, _COL_VEC),
                      out_dims=(_COL_BLOCK, _COL_BLOCK))(x2, bias)


def _gelu_bwd_launch(s2, dout2, approximate, in_dtype, interpret):
    def launch(s2, dout2):
        n, h = s2.shape
        hp = -(-h // 128) * 128
        blk = _tuned_row_block("fused_gelu", n, hp, in_dtype)
        row_spec = pl.BlockSpec((blk, hp), lambda i: (i, 0))
        vec_spec = pl.BlockSpec((1, hp), lambda i: (0, 0))
        dx, dbias = _pallas_call(
            functools.partial(_gelu_bwd_kernel, approximate=approximate),
            grid=(n // blk,),
            in_specs=[row_spec, row_spec],
            out_specs=[row_spec, vec_spec],
            out_shape=[jax.ShapeDtypeStruct((n, hp), in_dtype),
                       jax.ShapeDtypeStruct((1, hp), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((1, hp), jnp.float32)],
            interpret=interpret,
            name="fused_bias_gelu_bwd")(_pad_lanes(s2, hp),
                                        _pad_lanes(dout2, hp))
        return dx[:, :h], dbias[0, :h]
    return per_device(launch, in_dims=(_COL_BLOCK, _COL_BLOCK),
                      out_dims=(_COL_BLOCK, _COL_VEC), row_summed=(1,))(
                          s2, dout2)


# ----------------------------------------------------------------------
# custom-VJP apply ops (the _flash_apply pattern: identity forward,
# kernel backward off residuals that are named outputs — a
# names-saving remat policy then never re-runs the forward)
# ----------------------------------------------------------------------
def _flat_rows(x):
    return x.reshape(-1, x.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _ln_apply(y, bias, residual, gamma, beta, out, s,
              eps, use_pallas, interpret, sum_dtype):
    return out, s


def _ln_apply_fwd(y, bias, residual, gamma, beta, out, s,
                  eps, use_pallas, interpret, sum_dtype):
    del residual
    # zero-size dtype carriers: custom_vjp residuals must be arrays
    return (out, s), (s, gamma, jnp.zeros((0,), y.dtype),
                      jnp.zeros((0,), beta.dtype))


def _ln_apply_bwd(eps, use_pallas, interpret, sum_dtype, res, g):
    s, gamma, in_dt, param_dt = res
    in_dtype, param_dtype = in_dt.dtype, param_dt.dtype
    lead_shape = s.shape[:-1]
    d_out, d_sum = g
    h = s.shape[-1]
    s2 = _flat_rows(s)
    dout2 = _flat_rows(d_out)
    dsum2 = None if d_sum is None else _flat_rows(d_sum)
    if use_pallas:
        dx2, dbias, dgamma, dbeta = _ln_bwd_launch(
            s2, gamma, dout2, dsum2, eps, h, in_dtype, interpret)
    else:
        ds, dg_rows, dbeta_rows = _ln_bwd_math(
            s2, gamma, dout2, dsum2, eps, h)
        dx2 = ds.astype(in_dtype)
        dbias = jnp.sum(ds, axis=0).astype(param_dtype)
        dgamma = jnp.sum(dg_rows, axis=0).astype(param_dtype)
        dbeta = jnp.sum(dbeta_rows, axis=0).astype(param_dtype)
    dx = dx2.reshape(lead_shape + (h,))
    # y, bias (row-summed), residual share the chain cotangent; the
    # out/s operands came through the non-differentiable forward kernel
    return (dx, dbias.astype(param_dtype), dx.astype(sum_dtype),
            dgamma.astype(param_dtype), dbeta.astype(param_dtype),
            jnp.zeros_like(s, dtype=in_dtype), jnp.zeros_like(s))


_ln_apply.defvjp(_ln_apply_fwd, _ln_apply_bwd)


# Post-LN form: only the normalized output is returned, so no sum
# cotangent exists AT ALL.  (custom_vjp instantiates concrete zeros for
# an unused output's cotangent, so a two-output op would stream a full
# [N, H] zeros operand through the backward kernel on exactly the
# bytes-bound chain this module exists to shrink — a separate primal
# with one output keeps the d_sum path genuinely absent.)
@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _ln_apply_out(y, bias, residual, gamma, beta, out, s,
                  eps, use_pallas, interpret, sum_dtype):
    return out


def _ln_apply_out_fwd(y, bias, residual, gamma, beta, out, s,
                      eps, use_pallas, interpret, sum_dtype):
    del residual
    return out, (s, gamma, jnp.zeros((0,), y.dtype),
                 jnp.zeros((0,), beta.dtype))


def _ln_apply_out_bwd(eps, use_pallas, interpret, sum_dtype, res, g):
    grads = _ln_apply_bwd(eps, use_pallas, interpret, sum_dtype, res,
                          (g, None))
    return grads


_ln_apply_out.defvjp(_ln_apply_out_fwd, _ln_apply_out_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gelu_apply(x, bias, out, s, approximate, use_pallas, interpret):
    return out


def _gelu_apply_fwd(x, bias, out, s, approximate, use_pallas, interpret):
    return out, (s, jnp.zeros((0,), x.dtype), jnp.zeros((0,), bias.dtype))


def _gelu_apply_bwd(approximate, use_pallas, interpret, res, g):
    s, in_dt, param_dt = res
    in_dtype, param_dtype = in_dt.dtype, param_dt.dtype
    lead_shape = s.shape[:-1]
    h = s.shape[-1]
    s2 = _flat_rows(s)
    dout2 = _flat_rows(g)
    if use_pallas:
        dx2, dbias = _gelu_bwd_launch(s2, dout2, approximate,
                                      in_dtype, interpret)
    else:
        dx2 = _gelu_bwd_math(s2, dout2, approximate)
        dbias = jnp.sum(dx2, axis=0)
        dx2 = dx2.astype(in_dtype)
    dx = dx2.reshape(lead_shape + (h,))
    return (dx, dbias.astype(param_dtype),
            jnp.zeros_like(s, dtype=in_dtype), jnp.zeros_like(s))


_gelu_apply.defvjp(_gelu_apply_fwd, _gelu_apply_bwd)


# ----------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------
def fused_bias_residual_layernorm(y, bias, residual, gamma, beta, *,
                                  eps=1e-5, out_dtype=None,
                                  sum_dtype=None, impl="auto",
                                  return_sum=True):
    """out, resid_sum = LN((y + bias) + residual) * gamma + beta.

    `y` is a bias-less matmul output [..., H]; `bias`/`gamma`/`beta` are
    [H]; `residual` is the incoming stream [..., H].  One kernel launch
    computes the whole chain in fp32 and writes `out` (out_dtype,
    default y.dtype — feeds the next matmul) and `resid_sum` (sum_dtype,
    default residual.dtype — the pre-LN residual stream).  Both outputs
    carry checkpoint_name annotations ("fused_ln_out"/"fused_ln_sum")
    for the save_fused_epilogues remat policy; the backward needs ONLY
    the sum + gamma (mu/rstd are recomputed — cheap reductions), so a
    names-saving remat never re-runs this forward.

    return_sum=False (the post-LN wiring, where the normalized output
    IS the carry) returns just `out` through a single-output primal, so
    no sum cotangent ever exists — a dropped second output would
    otherwise stream a materialized zeros tensor through the backward
    kernel.
    """
    out_dtype = np.dtype(out_dtype) if out_dtype is not None else y.dtype
    sum_dtype = np.dtype(sum_dtype) if sum_dtype is not None \
        else residual.dtype
    use_pallas, interpret = _resolve_impl(impl)
    eps = float(eps)
    h = y.shape[-1]
    with jax.named_scope("fused_bias_residual_layernorm"):
        sg = jax.lax.stop_gradient
        if use_pallas:
            out2, s2 = _ln_fwd_launch(
                _flat_rows(sg(y)), sg(bias), _flat_rows(sg(residual)),
                sg(gamma), sg(beta), eps, h, out_dtype, sum_dtype,
                interpret)
            out = out2.reshape(y.shape)
            s = s2.reshape(y.shape)
        else:
            out_f, s_f = _ln_fwd_math(sg(y), sg(bias), sg(residual),
                                      sg(gamma), sg(beta), eps, h)
            out = out_f.astype(out_dtype)
            s = s_f.astype(sum_dtype)
        out = checkpoint_name(out, FUSED_LN_OUT)
        s = checkpoint_name(s, FUSED_LN_SUM)
        if not return_sum:
            return _ln_apply_out(y, bias, residual, gamma, beta, out, s,
                                 eps, use_pallas, interpret, sum_dtype)
        return _ln_apply(y, bias, residual, gamma, beta, out, s,
                         eps, use_pallas, interpret, sum_dtype)


def fused_bias_gelu(x, bias, *, approximate=False, out_dtype=None,
                    impl="auto"):
    """gelu(x + bias) as one launch; exact-erf by default (the
    reference kernel's form), `approximate=True` for the tanh form
    GPT-2 uses.  The bias+input sum is the only backward residual and
    carries the "fused_gelu_sum" checkpoint name (the save policy keeps
    it and recomputes the 4H-wide output with one transcendental
    pass)."""
    out_dtype = np.dtype(out_dtype) if out_dtype is not None else x.dtype
    use_pallas, interpret = _resolve_impl(impl)
    approximate = bool(approximate)
    with jax.named_scope("fused_bias_gelu"):
        sg = jax.lax.stop_gradient
        if use_pallas:
            out2, s2 = _gelu_fwd_launch(
                _flat_rows(sg(x)), sg(bias), approximate, out_dtype,
                x.dtype, interpret)
            out = out2.reshape(x.shape)
            s = s2.reshape(x.shape)
        else:
            out_f, s_f = _gelu_fwd_math(sg(x), sg(bias), approximate)
            out = out_f.astype(out_dtype)
            s = s_f.astype(x.dtype)
        s = checkpoint_name(s, FUSED_GELU_SUM)
        out = checkpoint_name(out, FUSED_GELU_OUT)
        return _gelu_apply(x, bias, out, s, approximate, use_pallas,
                           interpret)


def fused_ops_available():
    """(available, mode) for ds_report: the ops always work — the mode
    says whether they lower to Pallas kernels or the fused XLA form."""
    try:
        mode = "pallas-tpu" if _on_tpu() else "xla-fallback (no TPU)"
        return True, mode
    except Exception as e:  # pragma: no cover  # ds-lint: allow[BROADEXC] availability probe for ds_report: the failure text IS the report row
        return False, f"{type(e).__name__}: {e}"
