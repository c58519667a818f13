"""GPT-2 family — the flagship model for the TPU-native runtime.

The reference frames GPT-2 through Megatron integration
(`tests/model/Megatron_GPT2`); here the model is first-class and built for
XLA: one transformer block scanned over the layer dimension
(`nn.scan` → stacked [L, ...] params, single trace, pipeline-ready),
optional `nn.remat` activation checkpointing, bf16 compute with fp32
numerics where it matters (LayerNorm stats, softmax, loss), and
einsum-phrased attention that XLA tiles directly onto the MXU.

Tensor-parallel placement is expressed as PartitionSpec rules over the
param tree (`tp_param_specs`) — Megatron column/row parallel linear layers
(which the reference outsources to an external `mpu`,
`deepspeed/__init__.py:79-80`) become sharding annotations: qkv/fc kernels
column-sharded over `model`, proj kernels row-sharded, with XLA inserting
the psum that Megatron codes by hand.
"""

import dataclasses
import sys
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from deepspeed_tpu.ops import overlap as _overlap
from deepspeed_tpu.ops.transformer.flash_attention import (
    dense_attention, flash_attention, flash_attention_qkv,
    flash_attention_qkv_usable, flash_attention_rematerializable,
    flash_attention_usable)
from deepspeed_tpu.ops.transformer.quantized_matmul import (KERNEL_SCALE,
                                                            int8_matmul)
from deepspeed_tpu.runtime.mesh import EXPERT_AXIS, MODEL_AXIS
from deepspeed_tpu.utils.scopes import (SCOPE_ATTN_OUT, SCOPE_ATTN_QKV,
                                        SCOPE_MLP)


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.1
    layer_norm_epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16       # compute dtype
    param_dtype: Any = jnp.float32  # storage dtype of trainable params
    remat: bool = True              # activation-checkpoint each block
    # Selective rematerialisation: name of a jax.checkpoint_policies
    # policy (e.g. "dots_with_no_batch_dims_saveable" keeps weight-matmul
    # outputs and recomputes only the cheap elementwise chain) or None
    # for full-block remat.
    remat_policy: Optional[str] = None
    attention_impl: str = "auto"    # auto | pallas | xla
    # d=64 head packing in the flash kernel: "auto" pairs two heads per
    # grid step on real TPU so every score/output matmul contracts over
    # K=128 (the MXU's native width; unpacked d=64 runs half-starved),
    # "packed"/"off" force it. An odd head count leaves the last pair
    # one head.
    attention_head_packing: str = "auto"
    # Fused non-attention epilogues ("auto"|"on"|"off"): the block's
    # c_proj-bias + residual + ln_2 chain and the c_fc-bias + GeLU run
    # as single Pallas launches with a one-pass custom backward
    # (ops/transformer/fused_ops.py). "auto" fuses on real TPU when
    # dropout is inactive (backend-keyed like attention_head_packing);
    # "on" forces the path anywhere (XLA fallback off-TPU — same custom
    # VJP, same checkpoint names). The parameter tree is identical
    # either way. Pairs with remat_policy="save_fused_epilogues" for
    # per-fusion rematerialisation.
    fused_ops: str = "auto"
    # int8 quantized-compute projections ("off"|"on"|"auto"): the
    # block's four projection matmuls (c_attn, c_proj, c_fc,
    # mlp_c_proj) contract int8xint8 on the MXU with per-(K-block,
    # N-column) weight scales + per-row activation scales dequantized
    # in the GEMM epilogue (ops/transformer/quantized_matmul.py);
    # weights re-quantize inside every trace, the backward is
    # straight-through in the compute dtype. "auto" = real TPU only
    # (the fused_ops convention — CPU numerics stay bit-identical by
    # default); "off" is bit-for-bit the unquantized path. The
    # parameter tree is identical either way. Engine-wired via the
    # `quantized_compute` config block (configure_quantized_compute).
    quantized_compute: str = "off"
    quant_block: int = 128
    # round the int8 quantization stochastically when the engine
    # provides a per-step "quant" rng stream (unbiased; defaults to
    # round-to-nearest without one)
    quant_stochastic_rounding: bool = False
    # Sequence/context parallelism for long sequences: shard T over a
    # mesh axis and run ring (ppermute KV rotation) or ulysses
    # (all-to-all head swap) attention. Set sp_mesh to the engine mesh
    # and sp_axis to the axis carrying the sequence. By convention this
    # is the model axis, which the engine ALSO uses for Megatron-style
    # tensor parallelism (tp_param_specs): params stay TP-sharded while
    # activations enter attention seq-sharded — the usual TP+SP
    # composition, at the cost of a reshard on entry/exit per layer.
    sequence_parallel: Optional[str] = None   # None | "ring" | "ulysses"
    sp_mesh: Any = None
    sp_axis: str = "model"
    # Mixture-of-Experts (deepspeed_tpu/moe/): a MoEConfig makes every
    # `every_n_layers`-th block replace its dense MLP with the gated
    # top-k expert-parallel MoE MLP (router + capacity-factor
    # all-to-all dispatch + grouped-GEMM experts). STRUCTURAL — the
    # parameter tree changes for MoE layers (dense layers keep the
    # exact dense tree, so their weights load from dense
    # checkpoints); None is bit-for-bit the dense model. The engine's
    # `moe` config block wires the runtime knobs via `configure_moe`.
    moe: Any = None
    initializer_range: float = 0.02

    # what `InferenceEngine` reads off every model config: the kind of
    # cache the layers keep, and the module whose `embed`, `block`,
    # `head` and `layers` it composes with that kind's mixer
    cache_kind = "paged"
    serving_module = property(lambda self: sys.modules[__name__])

    @property
    def head_dim(self):
        assert self.n_embd % self.n_head == 0
        return self.n_embd // self.n_head

    @property
    def moe_cells(self):
        """Scan length of the MoE super-cell stack: each cell holds
        (every_n_layers - 1) dense blocks + one MoE block."""
        assert self.moe is not None
        every = self.moe.every_n_layers
        if self.n_layer % every:
            raise ValueError(
                f"moe.every_n_layers={every} must divide n_layer="
                f"{self.n_layer}")
        return self.n_layer // every


# Named model sizes (GPT-2 paper + GPT-3-style scale points used by the
# reference's Megatron benchmarks).
GPT2_SIZES = {
    # CI/harness size (tests/model/): real trajectories on a CPU mesh
    "gpt2-tiny": dict(n_layer=2, n_embd=64, n_head=4, vocab_size=512,
                      n_positions=128),
    "gpt2-125m": dict(n_layer=12, n_embd=768, n_head=12),
    "gpt2-350m": dict(n_layer=24, n_embd=1024, n_head=16),
    "gpt2-760m": dict(n_layer=24, n_embd=1536, n_head=16),
    "gpt2-1.5b": dict(n_layer=48, n_embd=1600, n_head=25),
    "gpt2-2.7b": dict(n_layer=32, n_embd=2560, n_head=32),
    "gpt2-6.7b": dict(n_layer=32, n_embd=4096, n_head=32),
    "gpt2-13b": dict(n_layer=40, n_embd=5120, n_head=40),
}


def gpt2_config(name="gpt2-125m", **overrides) -> GPT2Config:
    base = dict(GPT2_SIZES[name])
    base.update(overrides)
    return GPT2Config(**base)


def resolve_remat_policy(name):
    """Remat-policy string -> jax policy. Registered custom policies
    (incl. the built-in "save_fused_epilogues" per-fusion policy)
    resolve first, then `"save_only_these_names:a,b"` over
    `checkpoint_name` annotations (the model marks its attention output
    as "attn_out"), then `jax.checkpoint_policies` attributes."""
    from deepspeed_tpu.runtime.activation_checkpointing.checkpointing \
        import resolve_checkpoint_policy
    return resolve_checkpoint_policy(name)


def _dense(features, config, name, init_scale=1.0):
    return nn.Dense(
        features,
        dtype=config.dtype,
        param_dtype=config.param_dtype,
        kernel_init=nn.initializers.normal(config.initializer_range * init_scale),
        bias_init=nn.initializers.zeros,
        name=name)


def causal_attention_xla(q, k, v, dropout_rng=None, dropout_rate=0.0,
                         deterministic=True):
    """Plain XLA causal attention (shared dense_attention under the hood)."""
    return dense_attention(q, k, v, causal=True, dropout_rate=dropout_rate,
                           dropout_rng=dropout_rng,
                           deterministic=deterministic)


def _attention(config, qkv, dropout_rng, deterministic):
    """Attention over the `c_attn` product [B, T, 3·C] -> [B, T, C].
    Where the flash kernel can read q, k and v out of the product where
    it lies (C a whole number of its column tiles: seen in the shape),
    it gets the product whole and no slice of it is copied; everywhere
    else q, k and v are its three slices."""
    b, t, c = qkv.shape[0], qkv.shape[1], qkv.shape[2] // 3
    heads = (b, t, config.n_head, config.head_dim)

    def split():
        return tuple(x.reshape(heads) for x in jnp.split(qkv, 3, axis=-1))

    if config.sequence_parallel:
        # shard_map over the sequence axis composes inside the engine's
        # GSPMD step: activations reshard to [B, T/sp, H, D] on entry
        from deepspeed_tpu.ops.sequence import (ring_attention,
                                                ulysses_attention)
        assert config.sp_mesh is not None, \
            "sequence_parallel requires sp_mesh (pass the engine mesh)"
        assert deterministic or config.dropout == 0.0, \
            "attention dropout is not supported under sequence parallelism"
        impls = {"ring": ring_attention, "ulysses": ulysses_attention}
        if config.sequence_parallel not in impls:
            raise ValueError(
                f"sequence_parallel={config.sequence_parallel!r}; "
                f"valid values: {sorted(impls)} or None")
        fn = impls[config.sequence_parallel]
        return fn(*split(), mesh=config.sp_mesh,
                  axis_name=config.sp_axis, causal=True,
                  head_packing=config.attention_head_packing
                  ).reshape(b, t, c)
    if config.attention_impl in ("pallas", "auto"):
        no_dropout = deterministic or config.dropout == 0.0
        packing = config.attention_head_packing
        # under remat (out, lse) carry checkpoint_names: with a
        # save_only_these_names:attn_out,attn_lse policy the backward
        # never re-runs the flash fwd kernel
        if flash_attention_qkv_usable(qkv, config.n_head, no_dropout):
            return flash_attention_qkv(
                qkv, config.n_head, causal=True, head_packing=packing,
                rematerializable=config.remat).reshape(b, t, c)
        if flash_attention_usable(jax.ShapeDtypeStruct(heads, qkv.dtype),
                                  no_dropout):
            flash = flash_attention_rematerializable if config.remat \
                else flash_attention
            return flash(*split(), causal=True,
                         head_packing=packing).reshape(b, t, c)
        if config.attention_impl == "pallas":
            raise RuntimeError("pallas attention requested but unusable "
                               "for these shapes/settings")
    out = causal_attention_xla(*split(), dropout_rng, config.dropout,
                               deterministic)
    # keep the named residual on the XLA path too, so
    # save_only_these_names:attn_out policies behave uniformly (no lse
    # here — XLA attention has no separate softmax stats to save)
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(out, "attn_out").reshape(b, t, c)


def _quant_dense(features, cfg, name, init_scale=1.0, split=False,
                 sr_fallback=False):
    """QuantizedDense with nn.Dense/SplitDense-identical parameters —
    the quantized-compute twin of `_dense` (checkpoints interchange).
    sr_fallback=True is the family's backward-compatible bf16
    fallback: no quantization, stochastically rounded operand casts."""
    from deepspeed_tpu.ops.transformer.transformer import QuantizedDense
    return QuantizedDense(
        features, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        kernel_init=nn.initializers.normal(
            cfg.initializer_range * init_scale),
        bias_init=nn.initializers.zeros,
        quant_block=cfg.quant_block,
        stochastic_rounding=cfg.quant_stochastic_rounding,
        split=split, sr_fallback=sr_fallback, name=name)


class GPT2Block(nn.Module):
    """Pre-LN transformer block (attention + MLP).

    Boundary-fusion contract (tentpole of ISSUE 13(c) — after the
    fused epilogues, what is left unfused between two blocks is the
    mlp_c_proj-bias + residual-add + next-layer ln_1 chain, which
    this contract folds into one launch): when the
    caller passes `boundary=(prev_mlp_y, prev_mlp_b)` the TRUE hidden
    state is `hidden + prev_mlp_y + prev_mlp_b`, and this block folds
    that add into its leading LayerNorm as one fused
    bias+residual+LN launch. With `return_boundary=True` the block
    returns `(residual_stream, (mlp_y, mlp_b))` instead of completing
    its own trailing add — the next block (or the model's final
    fused ln_f) consumes it. The scan cell threads this carry; plain
    callers (pipe stages, eval helpers) keep the hidden-in/hidden-out
    interface with both args defaulted off."""
    config: GPT2Config

    @nn.compact
    def __call__(self, hidden, deterministic: bool = True,
                 boundary=None, return_boundary: bool = False):
        cfg = self.config
        b, t, c = hidden.shape

        from deepspeed_tpu.ops.transformer.fused_ops import (
            fused_bias_gelu, fused_bias_residual_layernorm,
            resolve_fused_ops)
        from deepspeed_tpu.ops.transformer.quantized_matmul import \
            resolve_quantized_compute
        # dropout sits between each projection's bias and the residual,
        # so the fused epilogues require it inactive
        use_fused = resolve_fused_ops(
            cfg.fused_ops, deterministic or cfg.dropout == 0.0)
        use_quant = resolve_quantized_compute(cfg.quantized_compute)
        if (boundary is not None or return_boundary) and not use_fused:
            raise ValueError(
                "GPT2Block boundary fusion requires the fused-ops path "
                "(resolve_fused_ops must be active for this trace)")

        def proj(features, name, init_scale=1.0, split=False):
            if use_quant:
                return _quant_dense(features, cfg, name,
                                    init_scale=init_scale, split=split)
            if cfg.quantized_compute not in ("off", False, 0, None) \
                    and cfg.quant_stochastic_rounding:
                # quantized compute configured but resolved OFF on
                # this backend, with stochastic_rounding: the
                # documented bf16 fallback — plain GEMM with
                # stochastically rounded operand casts
                return _quant_dense(features, cfg, name,
                                    init_scale=init_scale,
                                    split=split, sr_fallback=True)
            if split:
                from deepspeed_tpu.ops.transformer.transformer import \
                    SplitDense
                return SplitDense(
                    features, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    kernel_init=nn.initializers.normal(
                        cfg.initializer_range * init_scale),
                    name=name)
            return _dense(features, cfg, name, init_scale=init_scale)

        if use_fused:
            from deepspeed_tpu.ops.transformer.transformer import (
                LNParams, plain_layernorm)
            ln1_p = LNParams(param_dtype=cfg.param_dtype,
                             name="ln_1")(cfg.n_embd)
            ln2_p = LNParams(param_dtype=cfg.param_dtype,
                             name="ln_2")(cfg.n_embd)
        else:
            ln1 = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                               dtype=jnp.float32,
                               param_dtype=cfg.param_dtype, name="ln_1")
            ln2 = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                               dtype=jnp.float32,
                               param_dtype=cfg.param_dtype, name="ln_2")

        # --- attention ---
        if use_fused and boundary is not None:
            # one launch: previous block's mlp_c_proj bias + residual
            # + this block's ln_1 (the boundary chain); `hidden`
            # becomes the true residual stream
            prev_y, prev_b = boundary
            x, hidden = fused_bias_residual_layernorm(
                prev_y, prev_b, hidden, *ln1_p,
                eps=cfg.layer_norm_epsilon, out_dtype=cfg.dtype,
                sum_dtype=jnp.result_type(hidden.dtype, cfg.dtype))
        elif use_fused:
            x = plain_layernorm(hidden, *ln1_p,
                                eps=cfg.layer_norm_epsilon) \
                .astype(cfg.dtype)
        else:
            x = ln1(hidden).astype(cfg.dtype)
        qkv = proj(3 * cfg.n_embd, "c_attn")(x)
        drop_rng = None
        if not deterministic and cfg.dropout > 0.0:
            drop_rng = self.make_rng("dropout")
        # Under remat, the pallas path names its (out, lse) residuals
        # "attn_out"/"attn_lse" (flash_attention_rematerializable): a
        # "save_only_these_names:attn_out,attn_lse" policy then saves
        # ~27 MB/layer at 1.5B and the backward pass never re-runs the
        # flash forward kernel — the sweet spot between full remat
        # (+1 fwd of recompute) and dots_saveable (~235 MB/layer, OOM).
        attn = _attention(cfg, qkv, drop_rng, deterministic)
        if use_fused:
            attn_y, attn_b = proj(
                cfg.n_embd, "c_proj",
                init_scale=1.0 / np.sqrt(2 * cfg.n_layer),
                split=True)(attn)
            # one launch: c_proj bias + residual + ln_2; `hidden`
            # carries on un-normalized (pre-LN)
            y, hidden = fused_bias_residual_layernorm(
                attn_y, attn_b, hidden, *ln2_p,
                eps=cfg.layer_norm_epsilon, out_dtype=cfg.dtype,
                sum_dtype=jnp.result_type(hidden.dtype, cfg.dtype))
            fc_y, fc_b = proj(4 * cfg.n_embd, "c_fc", split=True)(y)
            # GPT-2 uses the tanh GeLU approximation
            y = fused_bias_gelu(fc_y, fc_b, approximate=True,
                                out_dtype=cfg.dtype)
            if return_boundary:
                # the trailing bias+residual add is NOT completed
                # here: the next block's fused ln_1 (or the model's
                # fused ln_f) consumes it as its boundary input
                mlp_y, mlp_b = proj(
                    cfg.n_embd, "mlp_c_proj",
                    init_scale=1.0 / np.sqrt(2 * cfg.n_layer),
                    split=True)(y)
                return hidden, (mlp_y, mlp_b)
            y = proj(cfg.n_embd, "mlp_c_proj",
                     init_scale=1.0 / np.sqrt(2 * cfg.n_layer))(y)
            return hidden + y
        # proj init scaled down by depth (GPT-2 residual-scaling trick)
        attn = proj(cfg.n_embd, "c_proj",
                    init_scale=1.0 / np.sqrt(2 * cfg.n_layer))(attn)
        attn = nn.Dropout(cfg.dropout)(attn, deterministic=deterministic)
        hidden = hidden + attn

        # --- MLP ---
        y = ln2(hidden).astype(cfg.dtype)
        y = proj(4 * cfg.n_embd, "c_fc")(y)
        y = nn.gelu(y, approximate=True)
        y = proj(cfg.n_embd, "mlp_c_proj",
                 init_scale=1.0 / np.sqrt(2 * cfg.n_layer))(y)
        y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return hidden + y


class MoEGPT2Block(nn.Module):
    """Pre-LN block whose MLP is the mixture-of-experts MoEMLP
    (deepspeed_tpu/moe/layer.py): attention half IDENTICAL to
    GPT2Block (same submodule names — ln_1/c_attn/c_proj/ln_2, so a
    dense checkpoint's attention weights load into an MoE model's MoE
    layers too), then router + dispatch + grouped-GEMM experts +
    combine instead of c_fc/mlp_c_proj. Returns (hidden, stats) —
    the [E+2] router stats vector the scan carry accumulates."""
    config: GPT2Config

    @nn.compact
    def __call__(self, hidden, deterministic: bool = True):
        cfg = self.config
        b, t, c = hidden.shape
        from deepspeed_tpu.moe.layer import MoEMLP
        from deepspeed_tpu.ops.transformer.quantized_matmul import \
            resolve_quantized_compute
        use_quant = resolve_quantized_compute(cfg.quantized_compute)

        def proj(features, name, init_scale=1.0):
            if use_quant:
                return _quant_dense(features, cfg, name,
                                    init_scale=init_scale)
            return _dense(features, cfg, name, init_scale=init_scale)

        ln1 = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                           dtype=jnp.float32,
                           param_dtype=cfg.param_dtype, name="ln_1")
        ln2 = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                           dtype=jnp.float32,
                           param_dtype=cfg.param_dtype, name="ln_2")

        x = ln1(hidden).astype(cfg.dtype)
        qkv = proj(3 * cfg.n_embd, "c_attn")(x)
        drop_rng = None
        if not deterministic and cfg.dropout > 0.0:
            drop_rng = self.make_rng("dropout")
        attn = _attention(cfg, qkv, drop_rng, deterministic)
        attn = proj(cfg.n_embd, "c_proj",
                    init_scale=1.0 / np.sqrt(2 * cfg.n_layer))(attn)
        attn = nn.Dropout(cfg.dropout)(attn, deterministic=deterministic)
        hidden = hidden + attn

        y = ln2(hidden).astype(cfg.dtype)
        y, stats = MoEMLP(
            moe=cfg.moe, d_model=cfg.n_embd, d_ff=4 * cfg.n_embd,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            out_kernel_init=nn.initializers.normal(
                cfg.initializer_range / np.sqrt(2 * cfg.n_layer)),
            name="moe_mlp")(y, deterministic)
        y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return hidden + y, stats


class _MoECellScan(nn.Module):
    """Scan cell of the MoE model: (every_n_layers - 1) dense
    GPT2Blocks — parameter-tree-identical to the dense model's
    blocks — followed by one MoEGPT2Block. Carry =
    (hidden, stats_sum): router stats accumulate across cells on
    device and surface once per step through the model loss, never
    per-layer host traffic. Also the cell the ZeRO-3 scheduled path
    applies per stacked slice (_zero3_loss), so the two traces run
    the same op sequence."""
    config: GPT2Config

    @nn.compact
    def __call__(self, carry, deterministic):
        cfg = self.config
        hidden, stats = carry
        block_cls = GPT2Block
        moe_cls = MoEGPT2Block
        if cfg.remat:
            block_cls = nn.remat(GPT2Block, prevent_cse=False,
                                 static_argnums=(2, 4),
                                 policy=resolve_remat_policy(
                                     cfg.remat_policy))
            moe_cls = nn.remat(MoEGPT2Block, prevent_cse=False,
                               static_argnums=(2,),
                               policy=resolve_remat_policy(
                                   cfg.remat_policy))
        for _ in range(cfg.moe.every_n_layers - 1):
            hidden = block_cls(cfg)(hidden, deterministic, None, False)
        hidden, s = moe_cls(cfg)(hidden, deterministic)
        return (hidden, stats + s), None


def embed_tokens(cfg: GPT2Config, wte, wpe, input_ids):
    """Token + position embedding in the compute dtype — the ONE
    definition of GPT-2's embedding arithmetic, shared by the module
    path and the ZeRO-3 scheduled path so they cannot drift."""
    t = input_ids.shape[1]
    return wte[input_ids].astype(cfg.dtype) + \
        wpe[:t][None, :, :].astype(cfg.dtype)


def stacked_block_params(params):
    """The nn.scan cell's stacked [n_layer, ...] param subtree — the
    single auto-named child under "h" (GPT2Block_0, or
    CheckpointGPT2Block_0 under remat; same leaves either way). The
    ONE place that naming knowledge lives: the ZeRO-3 scheduled loss
    and the inference engine's layer scan both reconstruct the block
    stack through this."""
    (_, stacked), = params["h"].items()
    return stacked


# ----------------------------------------------------------------------
# the model as it is served (`inference/engine.py` composes these with
# the paged cache's mixer and imports nothing from here). Training-math
# twins: the same flax modules the unfused training forward runs,
# applied to extracted param leaves (tests/test_serving_seam.py holds
# them to `GPT2ForCausalLM.apply`).
# ----------------------------------------------------------------------
layers = stacked_block_params

# the projections an int8 load quantises (wte, wpe, ln_* stay as stored)
QUANT_KERNEL_MODULES = ("c_attn", "c_proj", "c_fc", "mlp_c_proj")


def first_layers(cfg, params, n):
    """(config, params) of the model that is this one's first `n`
    blocks (a speculative draft): wte, wpe, ln_f and the tied head are
    the flagship's own buffers, the sliced stack the only new bytes."""
    (scan_key, stacked), = params["h"].items()
    sliced = jax.tree_util.tree_map(lambda x: x[:n], stacked)
    return (dataclasses.replace(cfg, n_layer=n),
            {**params, "h": {scan_key: sliced}})


def _ln_apply(cfg, p, x):
    """nn.LayerNorm exactly as GPT2Block builds it (fp32 stats)."""
    return nn.LayerNorm(
        epsilon=cfg.layer_norm_epsilon, dtype=jnp.float32,
        param_dtype=cfg.param_dtype).apply({"params": p}, x)


def _dense_apply(cfg, p, x):
    """nn.Dense as GPT2Block builds it — or, when the leaf carries a
    KERNEL_SCALE, the int8 dequant-in-matmul epilogue (the engine lays
    `inference.weight_quant_block` over `cfg.quant_block`)."""
    if KERNEL_SCALE in p:
        y = int8_matmul(x.astype(cfg.dtype), p["kernel"],
                        p[KERNEL_SCALE], cfg.quant_block, cfg.dtype)
        return y + p["bias"].astype(cfg.dtype)
    return nn.Dense(
        p["kernel"].shape[-1], dtype=cfg.dtype,
        param_dtype=cfg.param_dtype).apply(
            {"params": {"kernel": p["kernel"], "bias": p["bias"]}}, x)


def embed(cfg, params, tokens, positions):
    """`embed_tokens`' math at absolute positions."""
    return params["wte"][tokens].astype(cfg.dtype) + \
        params["wpe"][positions].astype(cfg.dtype)


def block(cfg, lp, hidden, positions, mixer, cache):
    """One pre-LN transformer block (GPT2Block's unfused math, op for
    op) over hidden [B, T, C] with one layer's leaves `lp`.
    `mixer(q, k, v, cache) -> (attn [B, T, C], cache)` attends over
    whatever the caller keeps of earlier tokens (the serving engine:
    the K/V page pools); q, k and v are as projected, heads side by
    side. Positions are learned and added in `embed`: not read here."""
    with jax.named_scope(SCOPE_ATTN_QKV):
        x = _ln_apply(cfg, lp["ln_1"], hidden).astype(cfg.dtype)
        qkv = _dense_apply(cfg, lp["c_attn"], x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
    attn, cache = mixer(q, k, v, cache)
    with jax.named_scope(SCOPE_ATTN_OUT):
        attn = _dense_apply(cfg, lp["c_proj"], attn)
        hidden = hidden + attn
    with jax.named_scope(SCOPE_MLP):
        y = _ln_apply(cfg, lp["ln_2"], hidden).astype(cfg.dtype)
        y = _dense_apply(cfg, lp["c_fc"], y)
        y = nn.gelu(y, approximate=True)
        y = _dense_apply(cfg, lp["mlp_c_proj"], y)
        hidden = hidden + y
    return hidden, cache


def head(cfg, params, hidden):
    """ln_f and the head tied to the embedding: [.., T, C] -> logits."""
    hidden = _ln_apply(cfg, params["ln_f"], hidden)
    return jnp.einsum("btc,vc->btv", hidden.astype(cfg.dtype),
                      params["wte"].astype(cfg.dtype))


class GPT2LMHeadModel(nn.Module):
    """GPT-2 with tied-embedding LM head; returns logits."""
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True,
                 layer_keep_prob: Optional[jnp.ndarray] = None,
                 return_hidden: bool = False):
        cfg = self.config
        b, t = input_ids.shape

        wte = self.param("wte",
                         nn.initializers.normal(cfg.initializer_range),
                         (cfg.vocab_size, cfg.n_embd), cfg.param_dtype)
        wpe = self.param("wpe",
                         nn.initializers.normal(cfg.initializer_range),
                         (cfg.n_positions, cfg.n_embd), cfg.param_dtype)

        hidden = embed_tokens(cfg, wte, wpe, input_ids)
        hidden = nn.Dropout(cfg.dropout)(hidden, deterministic=deterministic)

        if cfg.moe is not None:
            # MoE path: scan super-cells of (every_n - 1 dense blocks
            # + 1 MoE block); the carry threads (hidden, router-stats
            # sum) so per-layer stats reach the loss/monitor with zero
            # extra host traffic. Boundary fusion and PLD keep to the
            # dense path (the MoE combine boundary is not a fusable
            # bias+residual chain).
            if layer_keep_prob is not None:
                raise ValueError(
                    "progressive_layer_drop is not supported with "
                    "mixture-of-experts (no per-cell keep-prob gate)")
            cells = cfg.moe_cells
            ScannedCells = nn.scan(
                _MoECellScan,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True,
                            "quant": True},
                in_axes=(nn.broadcast,),
                length=cells,
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
            )
            stats0 = jnp.zeros((cfg.moe.num_experts + 2,), jnp.float32)
            (hidden, stats), _ = ScannedCells(cfg, name="h")(
                (hidden, stats0), deterministic)
            # per-MoE-layer mean: aux weighting and the fence event
            # stay depth-independent
            moe_stats = stats / jnp.float32(cells)
            hidden = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                                  dtype=jnp.float32,
                                  param_dtype=cfg.param_dtype,
                                  name="ln_f")(hidden)
            if return_hidden:
                return (hidden.astype(cfg.dtype), wte), moe_stats
            logits = jnp.einsum("btc,vc->btv",
                                hidden.astype(cfg.dtype),
                                wte.astype(cfg.dtype))
            return logits, moe_stats

        # Scan one block over a stacked [n_layer, ...] param tree: single
        # trace, O(1) compile in depth, and the layer dim is what pipeline
        # parallelism later splits across stages.
        ScannedBlocks = nn.scan(
            _BlockScanCell,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True, "quant": True},
            in_axes=(nn.broadcast, nn.broadcast),
            length=cfg.n_layer,
            metadata_params={nn.meta.PARTITION_NAME: "layers"},
        )
        # Progressive layer drop: stochastic depth with keep-prob theta fed
        # per step (ref `progressive_layer_drop.py:5`), applied as a
        # bernoulli gate on each block's residual inside the scan.
        keep = layer_keep_prob if layer_keep_prob is not None else None
        from deepspeed_tpu.ops.transformer.fused_ops import (
            fused_bias_residual_layernorm, resolve_fused_ops)
        # Boundary fusion (ISSUE 13(c)): under the fused path each
        # layer boundary's mlp_c_proj-bias + residual-add + next ln_1
        # runs as ONE fused launch — the scan carries
        # (residual_stream, (mlp_y, mlp_b)) instead of the completed
        # hidden state, and the final boundary folds into a fused
        # ln_f the same way. PLD gates on completed block outputs, so
        # it keeps the plain carry.
        use_boundary = keep is None and resolve_fused_ops(
            cfg.fused_ops, deterministic or cfg.dropout == 0.0)
        if use_boundary:
            from deepspeed_tpu.ops.transformer.transformer import \
                LNParams
            # the zero bias seeds the first boundary; its dtype must
            # match the bias params AS APPLIED (the engine hands the
            # compute-dtype cast of the tree to bf16 traces), which
            # wte's runtime dtype tracks exactly
            carry0 = (hidden,
                      (jnp.zeros(hidden.shape, cfg.dtype),
                       jnp.zeros((cfg.n_embd,), wte.dtype)))
            (resid, (mlp_y, mlp_b)), _ = ScannedBlocks(
                cfg, name="h")(carry0, deterministic, keep)
            lnf_p = LNParams(param_dtype=cfg.param_dtype,
                             name="ln_f")(cfg.n_embd)
            hidden = fused_bias_residual_layernorm(
                mlp_y, mlp_b, resid, *lnf_p,
                eps=cfg.layer_norm_epsilon, out_dtype=jnp.float32,
                return_sum=False)
        else:
            hidden, _ = ScannedBlocks(cfg, name="h")(hidden,
                                                     deterministic,
                                                     keep)
            hidden = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                                  dtype=jnp.float32,
                                  param_dtype=cfg.param_dtype,
                                  name="ln_f")(hidden)
        if return_hidden:
            # fused-head path: the caller computes loss chunkwise against
            # wte without materialising [B, T, vocab] logits
            return hidden.astype(cfg.dtype), wte
        logits = jnp.einsum("btc,vc->btv", hidden.astype(cfg.dtype),
                            wte.astype(cfg.dtype))
        return logits


class _BlockScanCell(nn.Module):
    """Scan cell: threads the carry through one (optionally rematted,
    optionally stochastic-depth-gated) block; returns (carry, None).

    Two carry shapes: a plain hidden array (the historical interface;
    PLD and the unfused path), or the boundary-fused tuple
    (residual_stream, (mlp_y, mlp_b)) — the block then folds the
    previous boundary into its fused ln_1 and leaves its own boundary
    open for the next cell (see GPT2Block's boundary contract)."""
    config: GPT2Config

    @nn.compact
    def __call__(self, carry, deterministic, keep_prob):
        cfg = self.config
        boundary_mode = isinstance(carry, tuple)
        block_cls = GPT2Block
        if cfg.remat:
            # static argnums index flax-remat call args with the
            # module at 0: deterministic=2, return_boundary=4
            block_cls = nn.remat(GPT2Block, prevent_cse=False,
                                 static_argnums=(2, 4),
                                 policy=resolve_remat_policy(
                                     cfg.remat_policy))
        if boundary_mode:
            hidden, prev = carry
            return block_cls(cfg)(hidden, deterministic, prev,
                                  True), None
        hidden = carry
        out = block_cls(cfg)(hidden, deterministic, None, False)
        if keep_prob is not None:
            if deterministic:
                out = hidden + keep_prob * (out - hidden)
            else:
                gate = jax.random.bernoulli(self.make_rng("dropout"),
                                            keep_prob)
                out = jnp.where(gate, out, hidden)
        return out, None


def chunked_tied_head_loss(hidden, wte, labels, ignore_index=-100,
                           chunk_tokens=1024):
    """Tied-embedding LM head + token CE without ever materialising the
    full [B, T, vocab] logits (at 50k vocab that is gigabytes in fp32 and
    was the single biggest activation in the train step).

    Scans over token chunks: each step computes a [chunk, vocab] logits
    tile on the MXU with fp32 accumulation, reduces it to (nll_sum,
    valid_count), and is `jax.checkpoint`-ed so the backward pass
    recomputes the tile instead of saving it.
    """
    b, t, c = hidden.shape
    n = b * t
    h = hidden.reshape(n, c)
    lab = labels.reshape(n)
    pad = (-n) % chunk_tokens
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, c), h.dtype)])
        lab = jnp.concatenate(
            [lab, jnp.full((pad,), ignore_index, lab.dtype)])
    h = h.reshape(-1, chunk_tokens, c)
    lab = lab.reshape(-1, chunk_tokens)
    wte_c = wte.astype(hidden.dtype)

    @jax.checkpoint
    def body(carry, xs):
        hc, lc = xs
        logits = jnp.einsum("tc,vc->tv", hc, wte_c,
                            preferred_element_type=jnp.float32)
        valid = lc != ignore_index
        safe = jnp.where(valid, lc, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[:, None], axis=1)[:, 0]
        nll = jnp.where(valid, logz - gold, 0.0)
        return (carry[0] + nll.sum(), carry[1] + valid.sum()), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0)), (h, lab))
    return total / jnp.maximum(count, 1)


def _zero3_leaf_depend(sched, tree, hidden):
    """`depend=` for a ZeRO-3 standalone-leaf gather under the
    `zero3_leaf` overlap site (ops/overlap.py): tying the gather to
    the post-embed activation sinks its all-gather under the first
    scan layers instead of serializing at the program top. None when
    the site is off — the PR-9 up-front gather, bit-exact either way
    (the fence is a schedule constraint, not math)."""
    nbytes = sum(
        int(np.prod(np.shape(leaf))) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(tree))
    on = _overlap.schedule(_overlap.SITE_ZERO3_LEAF,
                           payload_bytes=nbytes,
                           mesh=sched.mesh)["overlap"]
    return hidden if on else None


def cross_entropy_loss(logits, labels, ignore_index=-100):
    """Token-level CE in fp32; mean over non-ignored positions."""
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None],
                               axis=-1).squeeze(-1)
    nll = (logz - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


class GPT2ForCausalLM:
    """Engine-facing wrapper: `loss_fn(params, batch, rngs)` protocol.

    batch = dict(input_ids=[B,T] int32, labels=[B,T] int32).  Labels are
    next-token targets (already shifted) or raw ids (shift internally when
    labels is None).
    """

    def __init__(self, config: GPT2Config):
        self.config = config
        self.module = GPT2LMHeadModel(config)
        # ZeRO-3 gather/release scheduler (runtime/zero/stage3.py),
        # bound by the engine when the effective zero stage is 3
        self._zero3 = None

    def bind_zero3_scheduler(self, sched):
        """Engine hook: weave (or unweave, sched=None) the explicit
        stage-3 gather scheduler through the loss path. The parameter
        tree is IDENTICAL either way — checkpoints interchange."""
        self._zero3 = sched

    def moe_info(self):
        """Engine-facing MoE summary (None = dense model): the keys
        the engine needs for the `moe` block verification, the router
        labels of the per-fence `router` event, and the moe_dispatch
        ledger multiplier."""
        moe = self.config.moe
        if moe is None:
            return None
        return dict(num_experts=moe.num_experts, top_k=moe.top_k,
                    capacity_factor=moe.capacity_factor,
                    aux_loss_weight=moe.aux_loss_weight,
                    every_n_layers=moe.every_n_layers,
                    jitter_eps=moe.jitter_eps,
                    width=self.config.n_embd,
                    moe_layers=self.config.moe_cells)

    def configure_moe(self, mesh=None, num_experts=None,
                      every_n_layers=None, top_k=None,
                      capacity_factor=None, aux_loss_weight=None,
                      jitter_eps=None, fused_dispatch=None):
        """Engine hook for the `moe` config block. Structural keys
        (num_experts, every_n_layers) are VERIFIED against the built
        model — they shape the parameter tree, so a mismatch is a
        config error, not a rebuild. Router knobs (top_k,
        capacity_factor, aux_loss_weight, jitter_eps, fused_dispatch)
        and the engine mesh are applied: they are trace-time behavior,
        the parameter tree is identical before and after."""
        moe = self.config.moe
        if moe is None:
            raise ValueError(
                "moe config block is enabled but the model was built "
                "without MoE structure; construct it with "
                "GPT2Config(moe=MoEConfig(...)) so the parameter tree "
                "carries the expert leaves")
        for key, want in (("num_experts", num_experts),
                          ("every_n_layers", every_n_layers)):
            have = getattr(moe, key)
            if want is not None and int(want) != have:
                raise ValueError(
                    f"moe.{key}={want} does not match the model's "
                    f"built structure ({have}); structural keys "
                    "cannot be reconfigured after init")
        updates = {}
        if mesh is not None:
            updates["mesh"] = mesh
        if top_k is not None:
            updates["top_k"] = int(top_k)
        if capacity_factor is not None:
            updates["capacity_factor"] = float(capacity_factor)
        if aux_loss_weight is not None:
            updates["aux_loss_weight"] = float(aux_loss_weight)
        if jitter_eps is not None:
            updates["jitter_eps"] = float(jitter_eps)
        if fused_dispatch is not None:
            updates["fused_dispatch"] = fused_dispatch
        moe = dataclasses.replace(moe, **updates).validate()
        self.config = dataclasses.replace(self.config, moe=moe)
        self.module = GPT2LMHeadModel(self.config)

    def configure_quantized_compute(self, mode, block=None,
                                    stochastic_rounding=None):
        """Engine hook for the `quantized_compute` config block:
        rebuild the module with the int8 quantized-compute projection
        family switched to `mode` ("off"|"on"|"auto"). The parameter
        tree is IDENTICAL either way — existing checkpoints load
        unchanged and the toggle can flip mid-run between traces."""
        from deepspeed_tpu.ops.transformer.quantized_matmul import \
            resolve_quantized_compute
        resolve_quantized_compute(mode)   # ValueError on bad mode
        updates = {"quantized_compute": mode}
        if block is not None:
            updates["quant_block"] = int(block)
        if stochastic_rounding is not None:
            updates["quant_stochastic_rounding"] = \
                bool(stochastic_rounding)
        self.config = dataclasses.replace(self.config, **updates)
        self.module = GPT2LMHeadModel(self.config)

    def init(self, rng, example_batch):
        input_ids = example_batch["input_ids"]
        variables = self.module.init({"params": rng, "dropout": rng},
                                     input_ids, True)
        return variables["params"]

    @staticmethod
    def _shifted_labels(batch):
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.concatenate(
                [input_ids[:, 1:],
                 jnp.full_like(input_ids[:, :1], -100)], axis=1)
        return input_ids, labels

    _zero3_dropout_warned = False
    _zero3_jitter_warned = False

    def _zero3_active(self, deterministic):
        """Scheduled-path gate: rng-consuming traces stay on the
        module path — the scheduled stack folds its own per-layer rng
        stream, which would silently change dropout masks (and MoE
        router-jitter draws) vs the module path, false-alarming an
        ABCorrectnessChecker A/B. The fused_ops/head_packing
        "auto = dropout-inactive" convention, applied to the gather
        schedule; moe.jitter_eps is the same kind of training-only
        rng consumer, so it gates identically."""
        if self._zero3 is None:
            return False
        jitter_active = (not deterministic and
                         self.config.moe is not None and
                         self.config.moe.jitter_eps > 0.0)
        if jitter_active:
            if not GPT2ForCausalLM._zero3_jitter_warned:
                GPT2ForCausalLM._zero3_jitter_warned = True
                from deepspeed_tpu.utils.logging import logger
                logger.warning(
                    "ZeRO-3 gather scheduler: moe.jitter_eps is "
                    "active, so this trace uses the module path "
                    "(implicit GSPMD gathers) to keep router-jitter "
                    "draws identical to the unscheduled engine; set "
                    "moe.jitter_eps=0.0 to get the scheduled "
                    "gather/release path for training")
            return False
        if deterministic or self.config.dropout == 0.0:
            return True
        if not GPT2ForCausalLM._zero3_dropout_warned:
            GPT2ForCausalLM._zero3_dropout_warned = True
            from deepspeed_tpu.utils.logging import logger
            logger.warning(
                "ZeRO-3 gather scheduler: dropout is active, so this "
                "trace uses the module path (implicit GSPMD gathers) "
                "to keep dropout streams identical to the unscheduled "
                "engine; set dropout=0.0 to get the scheduled "
                "gather/release path for training")
        return False

    def loss_fn(self, params, batch, rngs=None, deterministic=False,
                layer_keep_prob=None, return_router_stats=False):
        if self._zero3_active(deterministic):
            return self._zero3_loss(params, batch, rngs, deterministic,
                                    layer_keep_prob,
                                    return_router_stats)
        input_ids, labels = self._shifted_labels(batch)
        kwargs = {}
        if layer_keep_prob is not None:
            kwargs["layer_keep_prob"] = layer_keep_prob
        out = self.module.apply({"params": params}, input_ids,
                                deterministic,
                                rngs=rngs or {},
                                return_hidden=True, **kwargs)
        if self.config.moe is not None:
            (hidden, wte), stats = out
            return self._moe_loss(hidden, wte, labels, stats,
                                  return_router_stats)
        if return_router_stats:
            raise ValueError(
                "return_router_stats requires a model built with "
                "GPT2Config(moe=...)")
        hidden, wte = out
        return chunked_tied_head_loss(hidden, wte, labels)

    def _moe_loss(self, hidden, wte, labels, stats,
                  return_router_stats):
        """CE + weighted aux load-balancing loss; `stats` is the
        per-MoE-layer mean [E+2] vector (aux at STAT_AUX), so the
        weight is depth-independent."""
        from deepspeed_tpu.moe.router import STAT_AUX
        loss = chunked_tied_head_loss(hidden, wte, labels)
        loss = loss + jnp.float32(
            self.config.moe.aux_loss_weight) * stats[STAT_AUX]
        if return_router_stats:
            return loss, stats
        return loss

    def _moe_zero3_specs(self, stacked):
        """Per-leaf base PartitionSpecs of the stacked MoE cell tree
        for the ZeRO-3 scheduler: expert leaves keep their expert dim
        on the `expert` axis through gather/reduce-scatter (the
        gathered copy stays expert-sharded — gathering over data
        only); everything else gathers to full. None when the mesh
        carries no expert axis (nothing to preserve)."""
        from deepspeed_tpu.runtime.mesh import (EXPERT_AXIS,
                                                expert_axis_size)
        mesh = self.config.moe.mesh
        if mesh is None or expert_axis_size(mesh) <= 1:
            return None
        flat, treedef = jax.tree_util.tree_flatten_with_path(stacked)
        specs = []
        for path, leaf in flat:
            name = jax.tree_util.keystr(path)
            spec = [None] * np.ndim(leaf)
            # stacked expert leaves: [cells, E, ...] — dim 1 is the
            # expert dim
            if "experts" in name and np.ndim(leaf) >= 3:
                spec[1] = EXPERT_AXIS
            specs.append(PartitionSpec(*spec))
        return jax.tree_util.tree_unflatten(treedef, specs)

    def _zero3_moe_loss(self, params, batch, rngs, deterministic,
                        return_router_stats):
        """The scheduled stage-3 forward of the MoE model: the whole
        super-cell subtree (dense blocks + MoE block — router,
        experts and all) is the stacked unit `apply_layers` drives, so
        expert leaves gather/reduce-scatter per layer window exactly
        like dense leaves, except their expert dim STAYS on the
        expert axis (param_specs below). The carry mirrors the module
        path's (hidden, stats) pair; op sequence identical."""
        cfg = self.config
        sched = self._zero3
        input_ids, labels = self._shifted_labels(batch)
        wte = sched.gather(params["wte"], name="wte")
        wpe = sched.gather(params["wpe"], name="wpe")
        hidden = embed_tokens(cfg, wte, wpe, input_ids)

        stacked = params["h"]
        cell = _MoECellScan(cfg)
        base_rng = (rngs or {}).get("dropout", jax.random.PRNGKey(0))
        lnf_params = sched.gather(
            params["ln_f"], name="ln_f",
            depend=_zero3_leaf_depend(sched, params["ln_f"], hidden))

        def body(lp, carry, rng_k):
            out, _ = cell.apply({"params": lp}, carry, deterministic)
            return out

        stats0 = jnp.zeros((cfg.moe.num_experts + 2,), jnp.float32)
        hidden, stats = sched.apply_layers(
            body, stacked, (hidden, stats0), base_rng, name="h",
            param_specs=self._moe_zero3_specs(stacked))
        stats = stats / jnp.float32(cfg.moe_cells)
        ln_f = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                            dtype=jnp.float32,
                            param_dtype=cfg.param_dtype)
        hidden = ln_f.apply({"params": lnf_params}, hidden)
        return self._moe_loss(hidden.astype(cfg.dtype), wte, labels,
                              stats, return_router_stats)

    def _zero3_loss(self, params, batch, rngs, deterministic,
                    layer_keep_prob, return_router_stats=False):
        """The scheduled stage-3 forward: same math as the module path
        (bit-exact at gather_dtype=None), but every parameter use goes
        through the scheduler — embeddings/ln_f gathered once for the
        step, the block stack driven by `apply_layers` so layer k+1's
        all-gather issues while layer k computes and each gathered
        buffer dies after its fwd/bwd use (full-block remat; the
        backward re-gathers in reverse order and reduce-scatters each
        layer's grad into its owning data-axis shard)."""
        if layer_keep_prob is not None:
            raise ValueError(
                "progressive_layer_drop is not supported on the ZeRO-3 "
                "scheduled path (the engine disables the scheduler "
                "when PLD is configured)")
        if self.config.moe is not None:
            return self._zero3_moe_loss(params, batch, rngs,
                                        deterministic,
                                        return_router_stats)
        if return_router_stats:
            raise ValueError(
                "return_router_stats requires a model built with "
                "GPT2Config(moe=...)")
        cfg = self.config
        sched = self._zero3
        input_ids, labels = self._shifted_labels(batch)
        # dropout-inactive by the _zero3_active gate: every dropout
        # layer is a no-op here, so no rng stream can diverge from the
        # module path
        wte = sched.gather(params["wte"], name="wte")
        wpe = sched.gather(params["wpe"], name="wpe")
        hidden = embed_tokens(cfg, wte, wpe, input_ids)

        stacked = stacked_block_params(params)
        block = GPT2Block(cfg)
        base_rng = (rngs or {}).get("dropout", jax.random.PRNGKey(0))
        from deepspeed_tpu.ops.transformer.fused_ops import (
            fused_bias_residual_layernorm, resolve_fused_ops)
        # mirror the module path's boundary fusion (dropout is
        # inactive here by the _zero3_active gate) so scheduled and
        # unscheduled traces run the same op sequence
        use_boundary = resolve_fused_ops(cfg.fused_ops, True)
        lnf_params = sched.gather(
            params["ln_f"], name="ln_f",
            depend=_zero3_leaf_depend(sched, params["ln_f"], hidden))

        if use_boundary:
            def body(lp, carry, rng_k):
                h, prev = carry
                return block.apply({"params": lp}, h, deterministic,
                                   prev, True)

            carry0 = (hidden,
                      (jnp.zeros(hidden.shape, cfg.dtype),
                       jnp.zeros((cfg.n_embd,), wte.dtype)))
            resid, (mlp_y, mlp_b) = sched.apply_layers(
                body, stacked, carry0, base_rng, name="h")
            hidden = fused_bias_residual_layernorm(
                mlp_y, mlp_b, resid, lnf_params["scale"],
                lnf_params["bias"], eps=cfg.layer_norm_epsilon,
                out_dtype=jnp.float32, return_sum=False)
        else:
            def body(lp, h, rng_k):
                return block.apply({"params": lp}, h, deterministic)

            hidden = sched.apply_layers(body, stacked, hidden,
                                        base_rng, name="h")
            ln_f = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                                dtype=jnp.float32,
                                param_dtype=cfg.param_dtype)
            hidden = ln_f.apply({"params": lnf_params}, hidden)
        return chunked_tied_head_loss(hidden.astype(cfg.dtype), wte,
                                      labels)

    def apply(self, params, input_ids, deterministic=True):
        out = self.module.apply({"params": params}, input_ids,
                                deterministic)
        if self.config.moe is not None:
            out, _stats = out   # logits only; stats ride loss_fn
        return out

    def sparse_grad_paths(self):
        """Param-path substrings whose grads are row-sparse, consumed by
        the engine's CSR gradient path (ref `engine.py:1190-1246`).
        Empty for GPT-2: the tied LM head makes the wte gradient DENSE
        (every vocab row receives softmax-normalizer gradient), so CSR
        compression would truncate it.  Models with pure-gather
        embeddings (untied heads) should return their embedding paths."""
        return ()

    # -- tensor parallel placement ---------------------------------------
    def tp_param_specs(self, params):
        """PartitionSpec tree: Megatron-style column/row sharding over the
        `model` mesh axis. Scanned blocks carry a leading layer dim."""
        from flax.traverse_util import flatten_dict, unflatten_dict
        from deepspeed_tpu.runtime.mesh import expert_axis_size
        flat = flatten_dict(params)
        moe = self.config.moe
        expert_sharded = (moe is not None and moe.mesh is not None and
                          expert_axis_size(moe.mesh) > 1)
        specs = {}
        for path, leaf in flat.items():
            name = "/".join(str(p) for p in path)
            nd = np.ndim(leaf)
            spec = [None] * nd
            if expert_sharded and "experts" in name and nd >= 3:
                # stacked expert leaves [cells, E, ...]: the expert
                # dim shards over the `expert` mesh axis; ZeRO's
                # data-axis sharding composes on a remaining free dim
                spec[1] = EXPERT_AXIS
                specs[path] = PartitionSpec(*spec)
                continue
            if name == "wte" or name == "wpe":
                # vocab/position dim sharded over model axis
                spec[0] = MODEL_AXIS
            elif "c_attn" in name and name.endswith("kernel"):
                spec[-1] = MODEL_AXIS          # column parallel
            elif "c_attn" in name and name.endswith("bias"):
                spec[-1] = MODEL_AXIS
            elif "c_fc" in name and name.endswith("kernel"):
                spec[-1] = MODEL_AXIS          # column parallel
            elif "c_fc" in name and name.endswith("bias"):
                spec[-1] = MODEL_AXIS
            elif "c_proj" in name and name.endswith("kernel"):
                spec[-2] = MODEL_AXIS          # row parallel
            specs[path] = PartitionSpec(*spec)
        return unflatten_dict(specs)


def tiny_gpt2_config(**overrides):
    """Small config for tests/CI (CPU-mesh friendly sizes)."""
    base = dict(vocab_size=256, n_positions=128, n_embd=64, n_layer=2,
                n_head=4, dropout=0.0, dtype=jnp.float32, remat=False)
    base.update(overrides)
    return GPT2Config(**base)
