"""MoEMLP: the drop-in mixture-of-experts MLP, plus its reference.

`MoEMLP` replaces a transformer block's dense MLP (c_fc + GeLU +
mlp_c_proj) with: a softmax top-k router, capacity-factor all-to-all
dispatch, grouped-GEMM expert FFNs, and gate-weighted combine. It
returns `(y, stats)` — the [E+2] router stats vector rides the scan
carry up to the model loss (aux load-balancing term) and on to the
monitor fence (the `router` event), never touching the host between
fences.

`moe_mlp_reference` is the unpacked oracle: the same gating math, but
a Python per-expert loop of single GEMMs with plain jnp epilogues —
no block-diagonal packing, no fused launches, no sharding
constraints. Parity against it (<=1e-5 fp32) is the tentpole's
correctness contract
(tests/test_moe.py).
"""

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.dispatch import (_mesh_active, combine_tokens,
                                        dispatch_buffer_nbytes,
                                        dispatch_tokens,
                                        record_dispatch_bytes,
                                        replicate_stats)
from deepspeed_tpu.moe.experts import ExpertFFN, expert_ffn_reference
from deepspeed_tpu.moe.fused_dispatch import (fused_combine,
                                              fused_dispatch,
                                              routing_slots)
from deepspeed_tpu.moe.router import (router_capacity, top_k_gating,
                                      top_k_gating_indexed)
from deepspeed_tpu.ops import overlap as _overlap


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Model-side MoE configuration (the engine's `moe` config block
    maps onto this via the model's `configure_moe` hook).

    num_experts / every_n_layers are STRUCTURAL — they shape the
    parameter tree, so the hook verifies rather than applies them.
    The router knobs (top_k, capacity_factor, aux_loss_weight,
    jitter_eps) are trace-time behavior and can change between traces
    without touching parameters. `mesh` carries the engine mesh so
    dispatch/combine can place the expert dimension on the `expert`
    axis (None = no sharding constraints, single-device semantics).
    `quantized_experts` ("off"|"on"|"auto") runs the expert
    projections through the PR-13 int8 quantized-compute family;
    `pack_experts` toggles the block-diagonal grouped-GEMM packing
    (False = the reference batched einsum; "auto" — the default —
    packs on real TPU only, the quantized-compute "auto" precedent:
    the packing trick exists to fill the MXU's 128-wide contraction
    lanes, while on XLA-CPU the traced block-diagonal assembly is
    pure overhead). `fused_dispatch` ("off"|"on"|"auto") swaps the
    one-hot dispatch/combine einsum pair for the fused gather-scatter
    kernels (moe/fused_dispatch.py); the fused path is local
    gather/scatter math, so "on" refuses expert-parallel meshes
    (their all-to-all IS the einsum pair's sharding constraint) and
    "auto" fuses only on a single TPU device (GSPMD cannot partition
    a Mosaic call)."""
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    every_n_layers: int = 1
    jitter_eps: float = 0.0
    quantized_experts: str = "off"
    quant_block: int = 128
    pack_experts: Any = "auto"
    fused_dispatch: Any = "auto"
    mesh: Any = None

    def validate(self):
        if self.num_experts < 2:
            raise ValueError(
                f"moe.num_experts must be >= 2, got {self.num_experts}")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(
                f"moe.top_k must be in [1, {self.num_experts}], got "
                f"{self.top_k}")
        if self.capacity_factor <= 0:
            raise ValueError(
                "moe.capacity_factor must be > 0, got "
                f"{self.capacity_factor}")
        if self.every_n_layers < 1:
            raise ValueError(
                "moe.every_n_layers must be >= 1, got "
                f"{self.every_n_layers}")
        if self.aux_loss_weight < 0 or self.jitter_eps < 0:
            raise ValueError(
                "moe.aux_loss_weight and moe.jitter_eps must be >= 0")
        if self.pack_experts not in (True, False, "auto"):
            raise ValueError(
                "moe.pack_experts must be True, False or 'auto', got "
                f"{self.pack_experts!r}")
        if self.fused_dispatch not in (True, False, "on", "off",
                                       "auto"):
            raise ValueError(
                "moe.fused_dispatch must be 'on', 'off' or 'auto', "
                f"got {self.fused_dispatch!r}")
        if self.fused_dispatch in (True, "on") and \
                _mesh_active(self.mesh):
            raise ValueError(
                "moe.fused_dispatch='on' is incompatible with an "
                "expert-parallel mesh: the einsum pair's sharding "
                "constraints are the all-to-all there; use 'auto' or "
                "'off'")
        return self


def resolve_pack_experts(mode):
    """`pack_experts` -> bool at trace time: True/False pass through;
    "auto" packs on real TPU only (the MXU-lane-filling trick; on
    XLA-CPU the traced block-diagonal assembly costs more than the
    halved GEMM count saves — measured in the moe_vs_dense leg)."""
    if mode is True or mode is False:
        return mode
    if mode == "auto":
        return jax.devices()[0].platform == "tpu"
    raise ValueError(
        f"pack_experts must be True, False or 'auto', got {mode!r}")


def resolve_fused_dispatch(mode, mesh=None):
    """`fused_dispatch` -> bool at trace time. "on"/True force the
    fused gather-scatter path (refused on expert-parallel meshes —
    validate() catches that earlier; re-checked here for direct
    callers); "auto" fuses on a single TPU device (any sharded program
    keeps the GSPMD einsum pair)."""
    if mode in (False, "off"):
        return False
    if mode in (True, "on"):
        if _mesh_active(mesh):
            raise ValueError(
                "fused_dispatch='on' is incompatible with an "
                "expert-parallel mesh (see MoEConfig.validate)")
        return True
    if mode == "auto":
        # one device only: the kernels gather over the whole batch's
        # slot table, and GSPMD cannot partition a Mosaic call
        # ("Mosaic kernels cannot be automatically partitioned"); a
        # sharded program keeps the einsum pair
        devices = mesh.size if mesh is not None else jax.device_count()
        return jax.devices()[0].platform == "tpu" and devices == 1
    raise ValueError(
        f"fused_dispatch must be 'on', 'off' or 'auto', got {mode!r}")


class MoEMLP(nn.Module):
    """Router + dispatch + grouped-GEMM experts + combine.

    Parameters: `wg` [H, E] router weights; `experts` (ExpertFFN)
    wi/bi/wo/bo with the expert dim leading. Input [B, T, H]; returns
    (y [B, T, H], stats [E+2]). Dropped tokens produce zeros — the
    caller's residual connection carries them through unchanged."""
    moe: MoEConfig
    d_model: int
    d_ff: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.normal(0.02)
    out_kernel_init: Callable = nn.initializers.normal(0.02)

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        moe = self.moe
        b, t, h = x.shape
        n = b * t
        wg = self.param("wg", self.kernel_init,
                        (h, moe.num_experts), self.param_dtype)
        xf = x.reshape(n, h)
        # router in fp32 (tiny GEMM; the gate decision must not move
        # with the compute dtype)
        logits = xf.astype(jnp.float32) @ wg.astype(jnp.float32)
        rng = None
        if not deterministic and moe.jitter_eps > 0.0 and \
                self.has_rng("dropout"):
            rng = self.make_rng("dropout")
        capacity = router_capacity(n, moe.num_experts, moe.top_k,
                                   moe.capacity_factor)
        # overlap schedule for the dispatch/combine pair: a pure
        # host-side read (explicit config > autotuned table > default;
        # ops/overlap.py). The payload class is the UNSHARDED buffer
        # bytes so init-time and engine traces agree.
        sched = _overlap.schedule(
            _overlap.SITE_MOE,
            payload_bytes=dispatch_buffer_nbytes(
                moe.num_experts, capacity, h, self.dtype, None),
            mesh=moe.mesh)
        fused = resolve_fused_dispatch(moe.fused_dispatch, moe.mesh)
        if fused:
            routing, stats = top_k_gating_indexed(
                logits, moe.top_k, capacity, rng=rng,
                jitter_eps=moe.jitter_eps)
        else:
            dispatch, combine, stats = top_k_gating(
                logits, moe.top_k, capacity, rng=rng,
                jitter_eps=moe.jitter_eps)
        # stats must stay replicated: the dispatched tensor's
        # (expert, data) sharding otherwise back-propagates into the
        # gating reductions and leaves per-shard PARTIAL sums (a
        # dp-times-too-large fetched vector; see replicate_stats)
        stats = replicate_stats(stats, moe.mesh)

        if fused:
            src, dest = routing_slots(routing, moe.num_experts,
                                      capacity)
            xe = fused_dispatch(xf.astype(self.dtype), src).reshape(
                moe.num_experts, capacity, h)
        else:
            xe = dispatch_tokens(xf.astype(self.dtype), dispatch,
                                 mesh=moe.mesh,
                                 granularity=sched["granularity"])
        if sched["overlap"]:
            # issue-early: the dispatch all-to-all (or gather) flies
            # while the router stats/aux epilogue computes
            xe, stats = _overlap.async_collective(xe, stats)
        ye = ExpertFFN(
            num_experts=moe.num_experts, d_model=h, d_ff=self.d_ff,
            dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=self.kernel_init,
            out_kernel_init=self.out_kernel_init,
            pack=resolve_pack_experts(moe.pack_experts),
            quantized=moe.quantized_experts,
            quant_block=moe.quant_block, name="experts")(xe)
        # trace-time byte accounting for the `moe_dispatch` ledger
        # category (host dict write, no device work). UNSHARDED bytes
        # by design: init-time traces run before a mesh is bound, so
        # the consumer applies its own mesh's per-device fraction
        # (dispatch_bytes_per_layer(mesh))
        record_dispatch_bytes(
            "/".join(self.path),
            dispatch_buffer_nbytes(moe.num_experts, capacity, h,
                                   self.dtype, None),
            num_experts=moe.num_experts, width=h)
        # in-flight window for the `overlap_inflight` ledger category:
        # the send + recv staging pair stays live across the overlap
        # region (0 when the site is not overlapped). PER-DEVICE bytes
        # — the mesh is known here; keyed so re-traces overwrite.
        _overlap.record_inflight(
            _overlap.SITE_MOE, "/".join(self.path),
            dispatch_buffer_nbytes(moe.num_experts, capacity, h,
                                   self.dtype, moe.mesh)
            if sched["overlap"] else 0)
        if fused:
            y = fused_combine(
                ye.reshape(moe.num_experts * capacity, h), dest,
                routing["keep"], routing["w"])
        else:
            y = combine_tokens(ye, combine, mesh=moe.mesh)
        if sched["overlap"]:
            # consume-late: the combined tokens release together with
            # the epilogue group, so the caller's post-expert residual
            # can overlap the combine collective
            y = _overlap.overlap_fence(y, stats)
        return y.reshape(b, t, h).astype(self.dtype), stats


def moe_mlp_reference(params, x, moe: MoEConfig, dtype=jnp.float32):
    """Unpacked per-expert-loop reference of MoEMLP.apply: same
    parameters, same gating, plain einsum dispatch, looped single-GEMM
    experts. The parity oracle (see module docstring)."""
    b, t, h = x.shape
    n = b * t
    xf = x.reshape(n, h)
    logits = xf.astype(jnp.float32) @ params["wg"].astype(jnp.float32)
    capacity = router_capacity(n, moe.num_experts, moe.top_k,
                               moe.capacity_factor)
    dispatch, combine, stats = top_k_gating(
        logits, moe.top_k, capacity)
    xe = jnp.einsum("nec,nh->ech", dispatch.astype(dtype),
                    xf.astype(dtype))
    ye = expert_ffn_reference(params["experts"], xe, dtype=dtype)
    y = jnp.einsum("nec,ech->nh", combine.astype(dtype), ye)
    return y.reshape(b, t, h).astype(dtype), stats
