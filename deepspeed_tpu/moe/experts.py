"""Expert FFNs as grouped GEMMs with block-diagonal expert packing.

The per-expert GEMM x_e [C, K] @ w_e [K, N] is small at production
expert counts (C = cf*k*tokens/E rows): the MXU runs half-starved on
narrow contractions exactly the way d=64 attention heads did before
PR 4 packed two of them block-diagonally into one K=128 contraction.
This module is the roadmap-named SECOND user of that trick, applied on
the expert dimension: experts (2g, 2g+1) fuse into one GEMM

    [x_2g | x_2g+1]  @  [[w_2g,    0   ],     ->  [y_2g | y_2g+1]
       [C, 2K]           [  0,  w_2g+1]]
                            [2K, 2N]

— half the GEMM count at double the contraction width, exact to fp
addition with zeros (the off-diagonal blocks contribute 0*x). An odd
expert count pads one zero expert. `pack=False` is the plain batched
einsum reference the parity tests pin against.

The epilogues reuse the PR-6 fused ops: bias+GeLU runs as the fused
launch vmapped over the expert dim (custom-VJP batching — Pallas adds
a grid dim on TPU, the XLA fallback vmaps the fused math), and the
optional int8 quantized experts vmap `quantized_dense` the same way
(PR-13's straight-through family, per-expert kernels quantized inside
the trace).
"""

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def grouped_gemm(x, w, *, pack=True, precision=None):
    """Batched per-group GEMM: x [G, M, K] @ w [G, K, N] -> [G, M, N].

    pack=True fuses group pairs block-diagonally (see module
    docstring); pack=False is the reference einsum. Both paths are
    trace-time graph construction only."""
    g, m, k = x.shape
    gw, kw, n = w.shape
    if gw != g or kw != k:
        raise ValueError(
            f"grouped_gemm shape mismatch: x {x.shape} vs w {w.shape}")
    if not pack or g < 2:
        return jnp.einsum("gmk,gkn->gmn", x, w, precision=precision)
    gp = g + (g % 2)
    if gp != g:
        x = jnp.concatenate(
            [x, jnp.zeros((1, m, k), x.dtype)], axis=0)
        w = jnp.concatenate(
            [w, jnp.zeros((1, k, n), w.dtype)], axis=0)
    # pair features: xp[g'] = [x_2g' | x_2g'+1]  -> [G/2, M, 2K]
    xp = jnp.concatenate([x[0::2], x[1::2]], axis=-1)
    # block-diagonal weights -> [G/2, 2K, 2N]
    wp = jnp.zeros((gp // 2, 2 * k, 2 * n), w.dtype)
    wp = wp.at[:, :k, :n].set(w[0::2])
    wp = wp.at[:, k:, n:].set(w[1::2])
    yp = jnp.einsum("gmk,gkn->gmn", xp, wp, precision=precision)
    # unsplit: [G/2, M, 2N] -> [G, M, N]
    y = jnp.stack([yp[..., :n], yp[..., n:]], axis=1) \
        .reshape(gp, m, n)
    return y[:g]


class ExpertFFN(nn.Module):
    """E parallel FFN experts over dispatched [E, C, H] buffers.

    Parameters (expert dim leading — the dim the `expert` mesh axis
    shards and ZeRO-3 gathers around):
      wi [E, H, F]   bi [E, F]     (up projection, fused bias+GeLU)
      wo [E, F, H]   bo [E, H]     (down projection)

    quantized != "off": the two projections run through PR-13's
    `quantized_dense` (int8 quantized-compute forward,
    straight-through backward) vmapped over experts, resolved per
    backend exactly like the dense family ("auto" = real TPU only).
    The parameter tree is identical either way.
    """
    num_experts: int
    d_model: int
    d_ff: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.normal(0.02)
    out_kernel_init: Callable = nn.initializers.normal(0.02)
    pack: bool = True
    quantized: str = "off"
    quant_block: int = 128

    @nn.compact
    def __call__(self, xe):
        e, c, h = xe.shape
        if e != self.num_experts or h != self.d_model:
            raise ValueError(
                f"ExpertFFN expects [E={self.num_experts}, C, "
                f"H={self.d_model}], got {xe.shape}")
        wi = self.param("wi", self.kernel_init,
                        (e, self.d_model, self.d_ff), self.param_dtype)
        bi = self.param("bi", nn.initializers.zeros,
                        (e, self.d_ff), self.param_dtype)
        wo = self.param("wo", self.out_kernel_init,
                        (e, self.d_ff, self.d_model), self.param_dtype)
        bo = self.param("bo", nn.initializers.zeros,
                        (e, self.d_model), self.param_dtype)
        xe = xe.astype(self.dtype)
        from deepspeed_tpu.ops.transformer.fused_ops import \
            fused_bias_gelu
        from deepspeed_tpu.ops.transformer.quantized_matmul import \
            resolve_quantized_compute
        if resolve_quantized_compute(self.quantized):
            from deepspeed_tpu.ops.transformer.quantized_matmul import \
                quantized_dense
            block = self.quant_block
            dtype = self.dtype

            def qmm(xg, wg):
                return quantized_dense(xg, wg.astype(dtype),
                                       block=block, out_dtype=dtype)
            yi = jax.vmap(qmm)(xe, wi)
        else:
            yi = grouped_gemm(xe, wi.astype(self.dtype),
                              pack=self.pack)
        # fused bias+GeLU epilogue, one launch per expert row-block
        # (vmap over the expert dim; GPT-2's tanh form)
        act = jax.vmap(
            lambda y, b: fused_bias_gelu(y, b, approximate=True,
                                         out_dtype=self.dtype))(
            yi, bi.astype(self.dtype))
        if resolve_quantized_compute(self.quantized):
            from deepspeed_tpu.ops.transformer.quantized_matmul import \
                quantized_dense
            block = self.quant_block
            dtype = self.dtype

            def qmm_o(xg, wg):
                return quantized_dense(xg, wg.astype(dtype),
                                       block=block, out_dtype=dtype)
            yo = jax.vmap(qmm_o)(act, wo)
        else:
            yo = grouped_gemm(act, wo.astype(self.dtype),
                              pack=self.pack)
        return yo + bo.astype(self.dtype)[:, None, :]


def expert_ffn_reference(params, xe, dtype=jnp.float32):
    """Unpacked per-expert-loop reference: a Python loop of single
    GEMMs + plain (jnp) bias/GeLU — no packing, no fused epilogues.
    The parity oracle for grouped_gemm/ExpertFFN
    (tests/test_moe.py)."""
    wi, bi = params["wi"], params["bi"]
    wo, bo = params["wo"], params["bo"]
    outs = []
    for g in range(np.shape(wi)[0]):
        y = xe[g].astype(dtype) @ wi[g].astype(dtype)
        y = jax.nn.gelu(y + bi[g].astype(dtype), approximate=True)
        outs.append(y @ wo[g].astype(dtype) + bo[g].astype(dtype))
    return jnp.stack(outs)
