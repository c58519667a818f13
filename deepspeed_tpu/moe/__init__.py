"""Mixture-of-Experts: expert-parallel routing, all-to-all dispatch,
and grouped-GEMM expert FFNs (ROADMAP item 2 — multiply parameters at
constant step FLOPs; the turn the upstream lineage shipped as
DeepSpeed-MoE after the v0.3.11 snapshot this repo reproduces).

The subsystem is GSPMD-declarative like the rest of the repo: routing,
dispatch and combine are einsums over global arrays with sharding
constraints placing the expert dimension on the `expert` mesh axis and
the capacity dimension on the `data` axis — XLA lowers the
(token-sharded -> expert-sharded) reshard pair to the dispatch/combine
all-to-alls inside the data-parallel device group (the DeepSpeed-MoE
communicator layout). Zero host syncs anywhere: router statistics stay
device-side and drain at the existing monitor fence.

  router.py    gated top-k token routing: softmax gate (fp32), optional
               logit jitter, capacity-factor dispatch/combine masks,
               Switch/GShard load-balancing aux loss, device-side
               router stats ([E+2]: per-expert load, drop frac, aux)
  dispatch.py  dispatch/combine einsum pair + sharding constraints +
               the trace-time byte accounting the `moe_dispatch`
               memory-ledger category samples
  fused_dispatch.py  the fused gather-scatter replacement for the
               einsum pair on expert-local meshes: Pallas
               scalar-prefetch kernels + an XLA take/segment-sum
               fallback sharing one custom VJP (`moe.fused_dispatch`
               config knob; ops/overlap.py schedules the pair)
  experts.py   expert FFNs as grouped GEMMs — pairs of experts packed
               block-diagonally so each GEMM contracts over 2*K (the
               PR-4 flash-attention packing trick's second user), with
               the fused bias+GeLU epilogue and optional int8
               QuantizedDense expert projections
  layer.py     `MoEMLP` — the flax module models drop in for a dense
               MLP — plus the unpacked per-expert-loop reference
               implementation the parity tests pin
               against

  serving.py   the layer as it is SERVED, a different contract and
               plain functions (no flax, nothing of the above):
               dropless top-k over experts that are all held or the
               share this chip is told it holds (`expert_layer(...,
               first_expert)`), sigmoid scores with a selection bias,
               normalised and scaled weights (`route`), rows sorted by
               expert (`sorted_by_expert`), one grouped product a
               projection over the experts held (`grouped_product`:
               `megablox.gmm` on a TPU, `lax.ragged_dot` elsewhere),
               gated SiLU experts and a shared one (`gated_mlp`);
               what a serving `block` calls (`models/trinity.py`)

See docs/moe.md for the routing math, capacity semantics, the ZeRO-3 /
elasticity composition contract, and the serving layer beside them.
"""

from deepspeed_tpu.moe.dispatch import (dispatch_bytes_per_layer,
                                        reset_dispatch_accounting)
from deepspeed_tpu.moe.experts import ExpertFFN, grouped_gemm
from deepspeed_tpu.moe.fused_dispatch import (fused_combine,
                                              fused_dispatch,
                                              routing_slots)
from deepspeed_tpu.moe.layer import (MoEConfig, MoEMLP,
                                     moe_mlp_reference,
                                     resolve_fused_dispatch,
                                     resolve_pack_experts)
from deepspeed_tpu.moe.router import (router_capacity, top_k_gating,
                                      top_k_gating_indexed,
                                      STAT_AUX, STAT_DROP)
from deepspeed_tpu.moe.serving import (expert_layer, gated_mlp,
                                       grouped_product, route,
                                       sorted_by_expert)

__all__ = [
    "MoEConfig", "MoEMLP", "ExpertFFN", "grouped_gemm",
    "moe_mlp_reference", "resolve_pack_experts",
    "resolve_fused_dispatch", "router_capacity",
    "top_k_gating", "top_k_gating_indexed", "fused_dispatch",
    "fused_combine", "routing_slots", "dispatch_bytes_per_layer",
    "reset_dispatch_accounting", "STAT_AUX", "STAT_DROP",
    # the layer as it is served (serving.py)
    "expert_layer", "route", "sorted_by_expert", "grouped_product",
    "gated_mlp",
]
