"""JSON config key constants and defaults.

Mirrors the public config surface of the reference
(`deepspeed/runtime/constants.py`) so that user configs written for the
reference work unchanged against the TPU-native runtime. Values are plain
string keys + defaults — the semantics are implemented TPU-first elsewhere.
"""

#############################################
# Routes
#############################################
ROUTE_TRAIN = "train"
ROUTE_EVAL = "eval"
ROUTE_PREDICT = "predict"
ROUTE_ENCODE = "encode"
ROUTES = (ROUTE_TRAIN, ROUTE_EVAL, ROUTE_PREDICT, ROUTE_ENCODE)

#############################################
# Batch size
#############################################
TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_BATCH_SIZE_DEFAULT = None

TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT = None

GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"
GRADIENT_ACCUMULATION_STEPS_DEFAULT = None

#############################################
# Optimizer and lr scheduler
#############################################
OPTIMIZER = "optimizer"
OPTIMIZER_TYPE_DEFAULT = None
OPTIMIZER_PARAMS = "params"
TYPE = "type"
LEGACY_FUSION = "legacy_fusion"
LEGACY_FUSION_DEFAULT = False
SCHEDULER = "scheduler"
SCHEDULER_TYPE_DEFAULT = None
SCHEDULER_PARAMS = "params"
MAX_GRAD_NORM = "max_grad_norm"

ZERO_ALLOW_UNTESTED_OPTIMIZER = "zero_allow_untested_optimizer"
ZERO_ALLOW_UNTESTED_OPTIMIZER_DEFAULT = False

# Optimizer type names accepted in the "optimizer" block.
ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
LAMB_OPTIMIZER = "lamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
SGD_OPTIMIZER = "sgd"
DEEPSPEED_OPTIMIZERS = [
    ADAM_OPTIMIZER,
    ADAMW_OPTIMIZER,
    LAMB_OPTIMIZER,
    ONEBIT_ADAM_OPTIMIZER,
    SGD_OPTIMIZER,
]

#############################################
# FP16 / mixed precision
#############################################
FP16 = "fp16"
FP16_ENABLED = "enabled"
FP16_ENABLED_DEFAULT = False
FP16_LOSS_SCALE = "loss_scale"
FP16_LOSS_SCALE_DEFAULT = 0
FP16_INITIAL_SCALE_POWER = "initial_scale_power"
FP16_INITIAL_SCALE_POWER_DEFAULT = 32
FP16_LOSS_SCALE_WINDOW = "loss_scale_window"
FP16_LOSS_SCALE_WINDOW_DEFAULT = 1000
FP16_HYSTERESIS = "hysteresis"
FP16_HYSTERESIS_DEFAULT = 2
FP16_MIN_LOSS_SCALE = "min_loss_scale"
FP16_MIN_LOSS_SCALE_DEFAULT = 1

# TPU-native extension: bfloat16 block (the natural TPU dtype; no loss
# scaling needed). Accepted as {"bf16": {"enabled": true}}.
BFLOAT16 = "bf16"
BFLOAT16_ALIAS = "bfloat16"
BFLOAT16_ENABLED = "enabled"
BFLOAT16_ENABLED_DEFAULT = False
# master_weights=false drops the fp32 master copy AND fp32 Adam moments
# for bf16 state + stochastic-rounded updates (runtime/bf16_optimizer.py)
# — 6 bytes/param of optimizer-side state instead of 16.
BFLOAT16_MASTER_WEIGHTS = "master_weights"
BFLOAT16_MASTER_WEIGHTS_DEFAULT = True

#############################################
# AMP (accepted for parity; maps onto bf16 autocast semantics on TPU)
#############################################
AMP = "amp"
AMP_ENABLED = "enabled"
AMP_ENABLED_DEFAULT = False

#############################################
# Gradient handling
#############################################
GRADIENT_CLIPPING = "gradient_clipping"
GRADIENT_CLIPPING_DEFAULT = 0.0

PRESCALE_GRADIENTS = "prescale_gradients"
PRESCALE_GRADIENTS_DEFAULT = False

GRADIENT_PREDIVIDE_FACTOR = "gradient_predivide_factor"
GRADIENT_PREDIVIDE_FACTOR_DEFAULT = 1.0

SPARSE_GRADIENTS = "sparse_gradients"
SPARSE_GRADIENTS_DEFAULT = False

FP32_ALLREDUCE = "fp32_allreduce"
FP32_ALLREDUCE_DEFAULT = False

ALLREDUCE_ALWAYS_FP32 = FP32_ALLREDUCE

DISABLE_ALLGATHER = "disable_allgather"
DISABLE_ALLGATHER_DEFAULT = False

#############################################
# Logging / monitoring
#############################################
STEPS_PER_PRINT = "steps_per_print"
STEPS_PER_PRINT_DEFAULT = 10

WALL_CLOCK_BREAKDOWN = "wall_clock_breakdown"
WALL_CLOCK_BREAKDOWN_DEFAULT = False

MEMORY_BREAKDOWN = "memory_breakdown"
MEMORY_BREAKDOWN_DEFAULT = False

DUMP_STATE = "dump_state"
DUMP_STATE_DEFAULT = False

TENSORBOARD = "tensorboard"
TENSORBOARD_ENABLED = "enabled"
TENSORBOARD_ENABLED_DEFAULT = False
TENSORBOARD_OUTPUT_PATH = "output_path"
TENSORBOARD_OUTPUT_PATH_DEFAULT = ""
TENSORBOARD_JOB_NAME = "job_name"
TENSORBOARD_JOB_NAME_DEFAULT = "DeepSpeedJobName"

#############################################
# Monitor block (TPU-native extension): unified async-safe telemetry —
# device-side metric accumulators drained at the async-dispatch sync
# fences, pluggable sinks (JSONL event log / native tfevents), step
# tracing, and a stall watchdog. See deepspeed_tpu/monitor/ and
# docs/monitoring.md.
#   {"monitor": {"enabled": true, "sinks": ["jsonl", "tensorboard"],
#                "output_path": "runs/x/monitor", "flush_interval": 0,
#                "stall_timeout_sec": 120, "stall_probe": false,
#                "all_ranks": false}}
#############################################
MONITOR = "monitor"
MONITOR_ENABLED = "enabled"
MONITOR_ENABLED_DEFAULT = False
MONITOR_SINKS = "sinks"
MONITOR_SINKS_DEFAULT = ("jsonl",)
MONITOR_OUTPUT_PATH = "output_path"
MONITOR_OUTPUT_PATH_DEFAULT = ""
MONITOR_JOB_NAME = "job_name"
MONITOR_JOB_NAME_DEFAULT = ""
MONITOR_FLUSH_INTERVAL = "flush_interval"
MONITOR_FLUSH_INTERVAL_DEFAULT = 0
MONITOR_STALL_TIMEOUT_SEC = "stall_timeout_sec"
MONITOR_STALL_TIMEOUT_SEC_DEFAULT = 0
MONITOR_STALL_PROBE = "stall_probe"
MONITOR_STALL_PROBE_DEFAULT = False
# Terminal stall verdict: after this many CONSECUTIVE watchdog fires
# with no intervening fence, emit one `stall_escalated` event (flight
# dump + sink event) and go quiet for the episode. 0 = off (one fire
# per stall episode, never terminal). The elastic supervisor
# (elasticity/runtime.py) treats the escalated event as "stop waiting,
# recover from the last committed checkpoint".
MONITOR_STALL_ESCALATE_AFTER = "stall_escalate_after"
MONITOR_STALL_ESCALATE_AFTER_DEFAULT = 0
MONITOR_ALL_RANKS = "all_ranks"
MONITOR_ALL_RANKS_DEFAULT = False
# MFU denominator override (FLOP/s per chip). 0 = auto: the chip's
# nominal bf16 peak on real TPUs, None (no MFU) on CPU/virtual meshes.
# Set it to make MFU / tokens_per_sec_per_chip meaningful on
# CPU-virtual-mesh rehearsal runs, or to report against a measured
# (rather than nominal) peak.
MONITOR_PEAK_FLOPS_OVERRIDE = "peak_flops_override"
MONITOR_PEAK_FLOPS_OVERRIDE_DEFAULT = 0.0

# -- monitor.trace: Perfetto/Chrome trace-event export ----------------
#   {"trace": {"enabled": true, "path": "", "max_events": 200000}}
# path defaults to <output_path>/trace_rank<r>.json; the file is
# written at monitor.close(), on a watchdog fire, and on demand via
# engine.monitor.export_trace(). bin/ds_trace merges per-rank shards.
MONITOR_TRACE = "trace"
MONITOR_TRACE_ENABLED = "enabled"
MONITOR_TRACE_ENABLED_DEFAULT = False
MONITOR_TRACE_PATH = "path"
MONITOR_TRACE_PATH_DEFAULT = ""
MONITOR_TRACE_MAX_EVENTS = "max_events"
MONITOR_TRACE_MAX_EVENTS_DEFAULT = 200000

# -- monitor.flight: crash/stall flight recorder ----------------------
#   {"flight": {"enabled": true, "capacity": 256, "path": ""}}
# A bounded in-memory ring of the last `capacity` monitor events +
# per-subsystem heartbeat ages, dumped atomically (tmp+fsync+rename)
# to flight_<ts>.json on watchdog fire, uncaught train_batch
# exception, SIGTERM, or abnormal interpreter exit. Enabled by default
# whenever the monitor is on (the ring is a deque append per event).
MONITOR_FLIGHT = "flight"
MONITOR_FLIGHT_ENABLED = "enabled"
MONITOR_FLIGHT_ENABLED_DEFAULT = True
MONITOR_FLIGHT_CAPACITY = "capacity"
MONITOR_FLIGHT_CAPACITY_DEFAULT = 256
MONITOR_FLIGHT_PATH = "path"
MONITOR_FLIGHT_PATH_DEFAULT = ""

# -- monitor.numerics: device-side numerics health --------------------
#   {"numerics": {"enabled": true}}
# Opt-in per-layer accumulators computed INSIDE the jitted step
# (grad-norm/abs-max/nonfinite per top-level param group, activation
# abs-max/mean/nonfinite at layer boundaries for layer-exposing
# models) and drained in the existing one-device_get-per-fence path —
# zero new per-step host syncs (guard-tested).
MONITOR_NUMERICS = "numerics"
MONITOR_NUMERICS_ENABLED = "enabled"
MONITOR_NUMERICS_ENABLED_DEFAULT = False

# -- monitor.memory: live HBM/host byte ledger ------------------------
#   {"memory": {"enabled": true, "top_buffers": 8}}
# ON by default with the monitor (like flight): every long-lived
# allocation site (engine state groups, offload host state, checkpoint
# snapshot double-buffers, prefetch staging, pipe 1F1B buffers)
# registers its logical bytes from shape metadata; each fence
# reconciles ledger vs device_memory_stats + host RSS into a `memory`
# event (residual = activations/XLA temporaries), tracks the peak
# watermark with the attribution snapshot AT peak, and renders
# Perfetto per-category counter tracks. RESOURCE_EXHAUSTED crashes get
# the ledger + top buffers + actionable hints attached to the flight
# dump. Zero new per-step host syncs (guard-tested).
MONITOR_MEMORY = "memory"
MONITOR_MEMORY_ENABLED = "enabled"
MONITOR_MEMORY_ENABLED_DEFAULT = True
MONITOR_MEMORY_TOP_BUFFERS = "top_buffers"
MONITOR_MEMORY_TOP_BUFFERS_DEFAULT = 8

#############################################
# Progressive layer drop
#############################################
PROGRESSIVE_LAYER_DROP = "progressive_layer_drop"
PLD_ENABLED = "enabled"
PLD_ENABLED_DEFAULT = False
PLD_THETA = "theta"
PLD_THETA_DEFAULT = 1.0
PLD_GAMMA = "gamma"
PLD_GAMMA_DEFAULT = 0.001

#############################################
# Checkpoint block: tag validation (reference parity) + the TPU-native
# zero-stall async save pipeline.
#   {"checkpoint": {"tag_validation": "Warn", "async_save": true,
#                   "keep_last": 0, "writer_queue_depth": 1,
#                   "queue_policy": "block"}}
# async_save: save_checkpoint costs the train loop only a device-side
#   snapshot (a jitted copy into fresh buffers the donating step
#   functions cannot alias, plus host-side copies of the ZeRO-Offload
#   master/moments/wire state); a background writer thread device_gets
#   and serializes shards into a `<tag>.tmp` staging dir, fsyncs,
#   atomically renames to `<tag>`, and updates `latest` last.
#   `engine.wait_for_checkpoint()` is the barrier (load_checkpoint
#   calls it implicitly).
# keep_last: rotation — keep only the newest N checkpoint dirs in
#   save_dir after each commit (0 = keep all). `latest`'s target is
#   never deleted.
# writer_queue_depth: async saves allowed in flight before
#   backpressure engages.
# queue_policy: what a save over the depth does — "block" waits for
#   the oldest in-flight save, "drop" discards the new save with a
#   warning (save_checkpoint returns False).
#############################################
CHECKPOINT = "checkpoint"
CHECKPOINT_TAG_VALIDATION = "tag_validation"
CHECKPOINT_TAG_VALIDATION_DEFAULT = "Warn"
CHECKPOINT_TAG_VALIDATION_MODES = ["Warn", "Ignore", "Fail"]
CHECKPOINT_ASYNC_SAVE = "async_save"
CHECKPOINT_ASYNC_SAVE_DEFAULT = True
CHECKPOINT_KEEP_LAST = "keep_last"
CHECKPOINT_KEEP_LAST_DEFAULT = 0
CHECKPOINT_WRITER_QUEUE_DEPTH = "writer_queue_depth"
CHECKPOINT_WRITER_QUEUE_DEPTH_DEFAULT = 1
CHECKPOINT_QUEUE_POLICY = "queue_policy"
CHECKPOINT_QUEUE_POLICY_DEFAULT = "block"
CHECKPOINT_QUEUE_POLICIES = ["block", "drop"]

#############################################
# Pipeline block (dict passed through to PipelineEngine)
#   {"pipeline": {"num_virtual_stages": 2}}
# num_virtual_stages (TPU-native extension): interleaved 1F1B — each
#   physical pipe stage hosts v round-robin model chunks
#   (Megatron-style virtual stages), cutting the fill/drain bubble from
#   (p-1)/(m+p-1) stage-times toward (p-1)/(v*m+p-1) at the cost of
#   more in-flight activations and a ~v-times-larger compiled schedule
#   (compile time grows accordingly — the 1F1B compile warning applies,
#   amplified). Requires pipe>1, gradient_accumulation_steps divisible
#   by the stage count, and at least pipe*v layers.
#############################################
PIPELINE = "pipeline"
PIPELINE_DEFAULT = {}
PIPELINE_NUM_VIRTUAL_STAGES = "num_virtual_stages"
PIPELINE_NUM_VIRTUAL_STAGES_DEFAULT = 1

#############################################
# Sparse attention
#############################################
SPARSE_ATTENTION = "sparse_attention"
SPARSE_DENSE_MODE = "dense"
SPARSE_FIXED_MODE = "fixed"
SPARSE_VARIABLE_MODE = "variable"
SPARSE_BIGBIRD_MODE = "bigbird"
SPARSE_BSLONGFORMER_MODE = "bslongformer"
SPARSE_MODE = "mode"
SPARSE_MODE_DEFAULT = SPARSE_FIXED_MODE
SPARSE_BLOCK = "block"
SPARSE_BLOCK_DEFAULT = 16
SPARSE_DIFFERENT_LAYOUT_PER_HEAD = "different_layout_per_head"
SPARSE_DIFFERENT_LAYOUT_PER_HEAD_DEFAULT = False
SPARSE_NUM_LOCAL_BLOCKS = "num_local_blocks"
SPARSE_NUM_LOCAL_BLOCKS_DEFAULT = 4
SPARSE_NUM_GLOBAL_BLOCKS = "num_global_blocks"
SPARSE_NUM_GLOBAL_BLOCKS_DEFAULT = 1
SPARSE_ATTENTION_TYPE = "attention"
SPARSE_ATTENTION_TYPE_DEFAULT = "bidirectional"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION = "horizontal_global_attention"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION_DEFAULT = False
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS = "num_different_global_patterns"
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS_DEFAULT = 1
SPARSE_NUM_RANDOM_BLOCKS = "num_random_blocks"
SPARSE_NUM_RANDOM_BLOCKS_DEFAULT = 0
SPARSE_LOCAL_WINDOW_BLOCKS = "local_window_blocks"
SPARSE_LOCAL_WINDOW_BLOCKS_DEFAULT = [4]
SPARSE_GLOBAL_BLOCK_INDICES = "global_block_indices"
SPARSE_GLOBAL_BLOCK_INDICES_DEFAULT = [0]
SPARSE_GLOBAL_BLOCK_END_INDICES = "global_block_end_indices"
SPARSE_GLOBAL_BLOCK_END_INDICES_DEFAULT = None
SPARSE_NUM_SLIDING_WINDOW_BLOCKS = "num_sliding_window_blocks"
SPARSE_NUM_SLIDING_WINDOW_BLOCKS_DEFAULT = 3

SPARSE_MODE_VALID = (
    SPARSE_DENSE_MODE,
    SPARSE_FIXED_MODE,
    SPARSE_VARIABLE_MODE,
    SPARSE_BIGBIRD_MODE,
    SPARSE_BSLONGFORMER_MODE,
)
# the full sparse block surface: the block is passed through wholesale
# to the SparsityConfig constructors (ops/sparse_attention), so config
# parsing validates against this list instead of reading each key
SPARSE_ATTENTION_KEYS = (
    SPARSE_MODE,
    SPARSE_BLOCK,
    SPARSE_DIFFERENT_LAYOUT_PER_HEAD,
    SPARSE_NUM_LOCAL_BLOCKS,
    SPARSE_NUM_GLOBAL_BLOCKS,
    SPARSE_ATTENTION_TYPE,
    SPARSE_HORIZONTAL_GLOBAL_ATTENTION,
    SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS,
    SPARSE_NUM_RANDOM_BLOCKS,
    SPARSE_LOCAL_WINDOW_BLOCKS,
    SPARSE_GLOBAL_BLOCK_INDICES,
    SPARSE_GLOBAL_BLOCK_END_INDICES,
    SPARSE_NUM_SLIDING_WINDOW_BLOCKS,
)

#############################################
# Elasticity (ref elasticity/constants.py) + model metadata
#############################################
ELASTICITY = "elasticity"
ELASTICITY_ENABLED = "enabled"
# model metadata consumed by the FLOPS profiler's MFU denominator
VOCABULARY_SIZE = "vocabulary_size"

#############################################
# TPU-native extensions (no reference analogue)
#############################################
# Mesh block: {"mesh": {"data": -1, "model": 1, "pipe": 1, "expert": 1}}.
# -1 = infer. The axis-name constants are the canonical names
# runtime/mesh.py builds the jax Mesh with. The `expert` axis exists
# only when the config names it (3-axis meshes stay byte-identical to
# the pre-MoE layout): batch data shards over (pipe, data, expert) —
# expert-parallel devices ARE data-parallel devices, the DeepSpeed-MoE
# convention — while expert parameters shard their expert dim over it
# (deepspeed_tpu/moe/).
MESH = "mesh"
MESH_DATA_AXIS = "data"
MESH_MODEL_AXIS = "model"
MESH_PIPE_AXIS = "pipe"
MESH_EXPERT_AXIS = "expert"

#############################################
# Mixture-of-Experts block (TPU-native extension; deepspeed_tpu/moe/):
# gated top-k token routing + capacity-factor all-to-all dispatch +
# expert-parallel grouped-GEMM FFNs, wired into supporting models
# (GPT-2 family) as a config-selectable MoE MLP.
#   {"moe": {"enabled": true, "num_experts": 8, "top_k": 2,
#            "capacity_factor": 1.25, "aux_loss_weight": 0.01,
#            "every_n_layers": 2, "jitter_eps": 0.0}}
# enabled: validate the block and wire the runtime knobs into the
#   model's `configure_moe` hook at engine init. The model must be
#   BUILT with a structurally matching moe config (num_experts /
#   every_n_layers change the parameter tree, so they are verified,
#   not applied); router knobs (top_k, capacity_factor,
#   aux_loss_weight, jitter_eps) are applied — they are trace-time
#   behavior, not structure.
# num_experts: experts per MoE layer. Must divide by the mesh `expert`
#   axis size (each expert-parallel device group owns
#   num_experts/expert contiguous experts).
# top_k: experts each token routes to (gate probs renormalized over
#   the selected k).
# capacity_factor: per-expert buffer slots = ceil(cf * top_k * tokens
#   / num_experts); tokens overflowing an expert's capacity are
#   DROPPED (the residual stream carries them unchanged) and counted
#   in the per-fence `router` event.
# aux_loss_weight: weight of the load-balancing auxiliary loss
#   (Switch/GShard form: E * sum_e f_e * P_e) added to the model loss.
# every_n_layers: every n-th transformer block uses the MoE MLP
#   (n_layer must divide evenly); 1 = every block.
# jitter_eps: multiplicative uniform jitter on router logits during
#   training (0 = off).
# fused_dispatch: "on"|"off"|"auto" — swap the one-hot
#   dispatch/combine einsum pair for the fused gather-scatter kernels
#   (moe/fused_dispatch.py). "on" refuses expert-parallel meshes (the
#   einsum pair's sharding constraints ARE the all-to-all there);
#   "auto" fuses on real TPU without an expert mesh axis.
#############################################
MOE = "moe"
MOE_ENABLED = "enabled"
MOE_ENABLED_DEFAULT = False
MOE_NUM_EXPERTS = "num_experts"
MOE_NUM_EXPERTS_DEFAULT = 8
MOE_TOP_K = "top_k"
MOE_TOP_K_DEFAULT = 2
MOE_CAPACITY_FACTOR = "capacity_factor"
MOE_CAPACITY_FACTOR_DEFAULT = 1.25
MOE_AUX_LOSS_WEIGHT = "aux_loss_weight"
MOE_AUX_LOSS_WEIGHT_DEFAULT = 0.01
MOE_EVERY_N_LAYERS = "every_n_layers"
MOE_EVERY_N_LAYERS_DEFAULT = 1
MOE_JITTER_EPS = "jitter_eps"
MOE_JITTER_EPS_DEFAULT = 0.0
MOE_FUSED_DISPATCH = "fused_dispatch"
MOE_FUSED_DISPATCH_DEFAULT = "auto"
MOE_FUSED_DISPATCH_VALID = ("on", "off", "auto")

#############################################
# Async dispatch (TPU-native extension): keep N steps in flight.
#   {"async_dispatch": {"enabled": true, "steps_per_sync": 0,
#                       "prefetch_depth": 2}}
# enabled: compile the LR schedule into the jitted step (device-resident
#   function of the device step counter — no per-step host scalar
#   upload) and drop the per-step fp16 `device_get(overflow)` host sync;
#   the scheduler's overflow-skip semantics moves on-device (skipped
#   steps don't bump `global_steps`). Host-side metrics (lr mirror,
#   loss scale, TensorBoard) are fetched only at sync fences.
#   Disabled automatically under ZeRO-Offload (the host optimizer step
#   is inherently synchronous) and when a client lr_scheduler object is
#   passed (arbitrary host code can't be compiled into the step).
# steps_per_sync: fence cadence in optimizer steps; 0 = follow
#   steps_per_print.
# prefetch_depth: staged batches the background PrefetchLoader
#   (runtime/prefetch.py) keeps in flight ahead of the step loop.
#############################################
ASYNC_DISPATCH = "async_dispatch"
ASYNC_DISPATCH_ENABLED = "enabled"
ASYNC_DISPATCH_ENABLED_DEFAULT = True
ASYNC_DISPATCH_STEPS_PER_SYNC = "steps_per_sync"
ASYNC_DISPATCH_STEPS_PER_SYNC_DEFAULT = 0
ASYNC_DISPATCH_PREFETCH_DEPTH = "prefetch_depth"
ASYNC_DISPATCH_PREFETCH_DEPTH_DEFAULT = 2

#############################################
# ZeRO-Offload compressed wire (TPU-native extension): the host link is
# the bottleneck of the offload round trip, so the wire format is
# configurable under zero_optimization.offload_wire:
#   {"offload_wire": {"grad_bits": 8, "param_bits": 8, "warmup_steps": 0}}
# grad_bits (D2H gradients): 32 = native wire, exactly the legacy
#   behavior (bf16 when computing in bf16, fp32 otherwise); 16 = force
#   bf16; 8 = int8 with a per-block fp32 scale; 1 = sign bits + one
#   per-block scale with on-device error feedback (1-bit Adam's
#   compression, runtime/fp16/onebit_adam.py).
# param_bits (H2D updated params): 32 = native (legacy); 8 = int8
#   param-delta against a device-resident fp32 param copy, with
#   host-side error feedback via a shadow copy.
# warmup_steps: steps that run a full-precision fp32 wire before
#   compression engages (error feedback starts from a settled state).
#############################################
OFFLOAD_WIRE = "offload_wire"
OFFLOAD_WIRE_GRAD_BITS = "grad_bits"
OFFLOAD_WIRE_GRAD_BITS_DEFAULT = 32
OFFLOAD_WIRE_PARAM_BITS = "param_bits"
OFFLOAD_WIRE_PARAM_BITS_DEFAULT = 32
OFFLOAD_WIRE_WARMUP_STEPS = "warmup_steps"
OFFLOAD_WIRE_WARMUP_STEPS_DEFAULT = 0
OFFLOAD_WIRE_GRAD_BITS_VALID = (1, 8, 16, 32)
OFFLOAD_WIRE_PARAM_BITS_VALID = (8, 32)

#############################################
# ZeRO stage-3 runtime (TPU-native extension): the explicit
# gather/release scheduler for sharded compute params
# (runtime/zero/stage3.py), configured under zero_optimization.stage3:
#   {"stage3": {"prefetch_layers": 1, "release_after_use": true,
#               "gather_dtype": null}}
# enabled: weave the scheduler through supporting model apply paths
#   (GPT-2/BERT layer stacks, sequential PipelineModule chains); off =
#   params stay sharded with XLA-implicit gathers (no scheduling
#   control, no live-bytes bound).
# prefetch_layers: all-gathers issued ahead of use — layer k+N's
#   params gather while layer k computes; live full-param memory is
#   bounded by (prefetch_layers + 1) layers. 0 = gather at use.
# release_after_use: false = naive baseline (whole stack gathered up
#   front, held live through fwd+bwd; full stacked grad materializes
#   before one bulk reduce-scatter) — the other side of an A/B.
# gather_dtype: cast params to this dtype BEFORE the all-gather
#   (null = storage dtype; "bf16" halves gather bytes for fp32 params).
#############################################
STAGE3 = "stage3"
STAGE3_ENABLED = "enabled"
STAGE3_ENABLED_DEFAULT = True
STAGE3_PREFETCH_LAYERS = "prefetch_layers"
STAGE3_PREFETCH_LAYERS_DEFAULT = 1
STAGE3_RELEASE_AFTER_USE = "release_after_use"
STAGE3_RELEASE_AFTER_USE_DEFAULT = True
STAGE3_GATHER_DTYPE = "gather_dtype"
STAGE3_GATHER_DTYPE_DEFAULT = None
STAGE3_GATHER_DTYPE_VALID = (None, "fp32", "bf16", "fp16")

#############################################
# Quantized compute (TPU-native extension): int8 quantized-compute
# forward GEMMs as the third fused-ops epilogue family
# (ops/transformer/quantized_matmul.py) — per-(K-block, N-column)
# weight scales + per-row activation scales, dequant fused into the
# GEMM epilogue, straight-through backward in the compute dtype.
#   {"quantized_compute": {"enabled": true, "mode": "auto",
#                          "block": 128,
#                          "stochastic_rounding": false}}
# enabled: wire the family into supporting models at engine init (the
#   model's configure_quantized_compute hook; models without the hook
#   warn and stay unquantized).
# mode: "auto" quantizes on real TPU only (the fused_ops convention —
#   CPU numerics stay bit-identical by default); "on" forces the path
#   anywhere (XLA fallback reproduces the same quantization
#   numerics); "off" parks the config without unwiring it.
# block: quantization block along the contraction dim. Must be a
#   multiple of 128 on the Pallas path (int8 lane tiling).
# stochastic_rounding: round the int8 quantization stochastically
#   (unbiased) using the per-step "quant" rng stream the engine
#   threads next to "dropout"; also makes the no-quantization bf16
#   fallback use stochastically rounded fp32->bf16 operand casts.
#############################################
QUANTIZED_COMPUTE = "quantized_compute"
QUANTIZED_COMPUTE_ENABLED = "enabled"
QUANTIZED_COMPUTE_ENABLED_DEFAULT = False
QUANTIZED_COMPUTE_MODE = "mode"
QUANTIZED_COMPUTE_MODE_DEFAULT = "auto"
QUANTIZED_COMPUTE_MODE_VALID = ("auto", "on", "off")
QUANTIZED_COMPUTE_BLOCK = "block"
QUANTIZED_COMPUTE_BLOCK_DEFAULT = 128
QUANTIZED_COMPUTE_STOCHASTIC_ROUNDING = "stochastic_rounding"
QUANTIZED_COMPUTE_STOCHASTIC_ROUNDING_DEFAULT = False

#############################################
# Kernel block-size autotuner (TPU-native extension): measured
# grid/block shapes for the Pallas kernels (flash, packed flash,
# fused epilogues, quantized GEMM), persisted as a versioned JSON
# next to the jax compile cache and consulted transparently at trace
# time (ops/autotune.py). Entries carry the kernel module's source
# hash — a kernel edit invalidates them (defaults, one warning).
#   {"autotune": {"enabled": true, "table_path": ""}}
# enabled: consult the table at trace time (searches are explicit —
#   a caller of ops.autotune.search runs them; nothing
#   searches inside a training step).
# table_path: "" = next to the jax compilation cache
#   (autotune_table_v2.json), else an explicit JSON path.
#############################################
AUTOTUNE = "autotune"
AUTOTUNE_ENABLED = "enabled"
AUTOTUNE_ENABLED_DEFAULT = True
AUTOTUNE_TABLE_PATH = "table_path"
AUTOTUNE_TABLE_PATH_DEFAULT = ""

#############################################
# Communication/compute overlap runtime (TPU-native extension): the
# shared optimization_barrier discipline (ops/overlap.py) that phrases
# issue-early/consume-late schedules at the MoE all-to-all pair, the
# ring-attention send/recv chain, and ZeRO-3 standalone-leaf gathers.
# Bit-exact by construction — the barriers constrain the schedule,
# never the math.
#   {"overlap": {"enabled": true, "sites": "auto",
#                "issue_distance": 1}}
# enabled: master switch for the discipline (off = every site runs
#   its unscheduled baseline).
# sites: "auto" (default) consults the autotune collective-schedule
#   table per (site, mesh shape, payload bucket); or an explicit list
#   drawn from ["moe_dispatch", "ring", "zero3_leaf"] to pin exactly
#   which sites overlap.
# issue_distance: how many collective windows may stay in flight at
#   the ring site (>= 1); also the default the autotuner's candidates
#   are measured against. In-flight staging bytes are ledgered as the
#   `overlap_inflight` category (docs/monitoring.md).
#############################################
OVERLAP = "overlap"
OVERLAP_ENABLED = "enabled"
OVERLAP_ENABLED_DEFAULT = True
OVERLAP_SITES = "sites"
OVERLAP_SITES_DEFAULT = "auto"
OVERLAP_ISSUE_DISTANCE = "issue_distance"
OVERLAP_ISSUE_DISTANCE_DEFAULT = 1

#############################################
# Inference/serving engine (TPU-native extension): AOT-compiled
# prefill + single-token decode over a device-resident paged KV cache
# with continuous batching (deepspeed_tpu/inference/), configured
# under a top-level "inference" block:
#   {"inference": {"max_slots": 8, "prefill_chunk": 64,
#                  "sync_every": 8, "max_new_tokens": 128,
#                  "max_seq_len": null, "eos_token_id": null,
#                  "top_k_max": 64, "seed": 0,
#                  "weight_bits": 32, "weight_quant_block": 64,
#                  "kv_cache": {"num_pages": 256, "page_size": 16}}}
# max_slots: concurrent decode request slots — the decode program's
#   static batch dimension (iteration-level continuous batching admits
#   queued requests into slots that free up).
# prefill_chunk: prompt tokens processed per prefill program call;
#   long prompts run chunk-by-chunk INTERLEAVED with decode so they
#   never stall the decode batch.
# sync_every: decode iterations dispatched between serving fences (the
#   one device_get per fence; the async_dispatch steps_per_sync
#   convention applied to serving).
# max_new_tokens: per-request generation cap AND the device output
#   buffer width (requests may ask for less, never more).
# max_seq_len: prompt + generated upper bound (null = the model's
#   n_positions, clamped to kv_cache capacity).
# eos_token_id: default end-of-sequence id finishing a request early
#   (null = generate until max_new_tokens; per-request override).
# top_k_max: static top-k sampling cap compiled into the decode
#   program (per-request top_k <= top_k_max).
# seed: base PRNG seed for device-side sampling.
# weight_bits: 32 = serve the params as given; 8 = int8 weight-only
#   quantization at load (per-block-scale, the offload_wire block
#   machinery) with a dequant-in-matmul epilogue.
# weight_quant_block: quantization block along the contraction dim.
# kv_cache.num_pages: physical pages in the preallocated device pool
#   (page 0 is a scratch page for masked writes; num_pages - 1 are
#   allocatable). The pool is a `kv_cache` memory-ledger category.
# kv_cache.page_size: tokens per page.
#############################################
INFERENCE = "inference"
INFERENCE_MAX_SLOTS = "max_slots"
INFERENCE_MAX_SLOTS_DEFAULT = 8
INFERENCE_PREFILL_CHUNK = "prefill_chunk"
INFERENCE_PREFILL_CHUNK_DEFAULT = 64
INFERENCE_SYNC_EVERY = "sync_every"
INFERENCE_SYNC_EVERY_DEFAULT = 8
INFERENCE_MAX_NEW_TOKENS = "max_new_tokens"
INFERENCE_MAX_NEW_TOKENS_DEFAULT = 128
INFERENCE_MAX_SEQ_LEN = "max_seq_len"
INFERENCE_MAX_SEQ_LEN_DEFAULT = None
INFERENCE_EOS_TOKEN_ID = "eos_token_id"
INFERENCE_EOS_TOKEN_ID_DEFAULT = None
INFERENCE_TOP_K_MAX = "top_k_max"
INFERENCE_TOP_K_MAX_DEFAULT = 64
INFERENCE_SEED = "seed"
INFERENCE_SEED_DEFAULT = 0
INFERENCE_WEIGHT_BITS = "weight_bits"
INFERENCE_WEIGHT_BITS_DEFAULT = 32
INFERENCE_WEIGHT_BITS_VALID = (8, 32)
INFERENCE_WEIGHT_QUANT_BLOCK = "weight_quant_block"
INFERENCE_WEIGHT_QUANT_BLOCK_DEFAULT = 64
INFERENCE_KV_CACHE = "kv_cache"
INFERENCE_KV_NUM_PAGES = "num_pages"
INFERENCE_KV_NUM_PAGES_DEFAULT = 256
INFERENCE_KV_PAGE_SIZE = "page_size"
INFERENCE_KV_PAGE_SIZE_DEFAULT = 16
#############################################
# Serving observability (ISSUE 14, monitor/serving.py).
# observability.enabled: build the per-request lifecycle tracker when
#   a monitor block is enabled on the same config (default true; the
#   monitor.flight / monitor.memory convention — no monitor, no
#   tracker). The tracker stamps request phases from host dispatch
#   timestamps at the existing serving fences only: zero new per-token
#   host syncs (the HOTSYNC contract).
# observability.slo_ttft_ms / slo_token_ms: latency targets for the
#   goodput split (tokens from requests meeting every configured
#   target vs all tokens). 0 = no target (goodput == throughput).
#############################################
INFERENCE_OBSERVABILITY = "observability"
INFERENCE_OBS_ENABLED = "enabled"
INFERENCE_OBS_ENABLED_DEFAULT = True
INFERENCE_OBS_SLO_TTFT_MS = "slo_ttft_ms"
INFERENCE_OBS_SLO_TTFT_MS_DEFAULT = 0.0
INFERENCE_OBS_SLO_TOKEN_MS = "slo_token_ms"
INFERENCE_OBS_SLO_TOKEN_MS_DEFAULT = 0.0
#############################################
# Speculative decoding (ISSUE 18, inference/speculative.py).
#   {"inference": {"speculative": {"enabled": false,
#                                  "draft_model": "truncate:1",
#                                  "k": 4,
#                                  "k_min": 1,
#                                  "adaptive": true}}}
# speculative.enabled: propose tokens with a cheap draft model and
#   verify k+1 positions per flagship launch (lossless: greedy
#   prefix-match at temperature 0, modified rejection sampling above —
#   the output distribution is exactly the vanilla decode one). The
#   default false leaves the engine's two compiled programs and its
#   outputs byte-for-byte unchanged.
# speculative.draft_model: where the draft comes from. "truncate:N"
#   derives it from the flagship's first N transformer layers (shared
#   embeddings / final LN / tied head — zero extra checkpoint);
#   "external" uses the draft_params/draft_model_config pair passed to
#   the InferenceEngine constructor.
# speculative.k: drafted tokens per round — the verify program's
#   static width is k+1 positions per slot.
# speculative.k_min: adaptive back-off floor (1 = degenerate to one
#   drafted token per round on hostile prompts).
# speculative.adaptive: per-slot k adaptation — a slot that accepts a
#   full round grows its k toward `k`, a slot whose acceptance EMA
#   drops below the back-off threshold shrinks toward `k_min`; the
#   host dispatches max(live k) draft steps per round, so a batch
#   whose drafts are all being rejected stops paying for them.
#############################################
INFERENCE_SPECULATIVE = "speculative"
INFERENCE_SPEC_ENABLED = "enabled"
INFERENCE_SPEC_ENABLED_DEFAULT = False
INFERENCE_SPEC_DRAFT_MODEL = "draft_model"
INFERENCE_SPEC_DRAFT_MODEL_DEFAULT = "truncate:1"
INFERENCE_SPEC_K = "k"
INFERENCE_SPEC_K_DEFAULT = 4
INFERENCE_SPEC_K_MIN = "k_min"
INFERENCE_SPEC_K_MIN_DEFAULT = 1
INFERENCE_SPEC_ADAPTIVE = "adaptive"
INFERENCE_SPEC_ADAPTIVE_DEFAULT = True
