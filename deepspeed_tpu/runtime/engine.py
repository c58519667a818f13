"""DeepSpeedEngine — the TPU-native training engine.

Counterpart of `deepspeed/runtime/engine.py:95` (1573 LoC of torch
mutation), redesigned around XLA's compilation model:

  * the whole training step — scaled loss, grads, microbatch
    accumulation, overflow vote, loss-scale automaton, clipping, optimizer
    update, param re-cast — is ONE jitted function (`_train_step_fn`).
    The reference's engine.forward/backward/step + ZeRO hook pipeline
    (`engine.py:796-1078`, `stage2.py:583-1489`) becomes a single traced
    program; XLA's latency-hiding scheduler supplies the comm/compute
    overlap that `overlap_comm` hand-builds with CUDA streams.
  * data parallelism needs no allreduce code: the batch is sharded over
    the `data` mesh axis, grads of the global-mean loss are globally
    averaged by construction (GSPMD inserts the reductions; cf. the
    manual bucketed allreduce at `engine.py:1115-1188`).
  * ZeRO-1/2/3 are sharding policies on the optimizer/grad/param state
    (see `runtime/zero/partition.py`), not separate optimizer classes.
  * fp16 dynamic loss scaling runs fully on-device (`lax.cond`-guarded
    update) — the overflow decision never leaves the chip unless fp16
    stats are being reported (ref does a Python-side skip,
    `stage2.py:1346-1368`).
  * async dispatch (default on): the LR schedule is a device-resident
    function of the device `global_steps` counter compiled into the
    step, so the hot loop performs NO host<->device synchronization —
    no per-step lr upload, no `device_get(overflow)` (overflow-skipped
    steps simply don't bump `global_steps`, which IS the reference's
    "scheduler doesn't advance past an overflow step" semantics).
    Host-side metrics sync only at `steps_per_sync` fences; batches
    prefetch on a background thread (`runtime/prefetch.py`).

The three-call API (`engine(batch)` / `engine.backward(loss)` /
`engine.step()`) is preserved for drop-in compatibility; `train_batch`
(one fused step over all grad-accum microbatches) is the fast path.
"""

import contextlib
import copy
import os
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.mesh import (DATA_AXIS, EXPERT_AXIS,
                                        MODEL_AXIS, PIPE_AXIS,
                                        batch_axes, build_mesh,
                                        data_sharding, expert_axis_size,
                                        replicated, stacked_batch_pspecs)
from deepspeed_tpu.runtime.utils import _zeros_like_f32
from deepspeed_tpu.runtime.zero.partition import ZeroShardingPolicy
from deepspeed_tpu.runtime.zero.offload import ZeroOffloadMixin
from deepspeed_tpu.runtime.fp16.loss_scaler import (
    LossScaleState, make_loss_scale_state, make_static_loss_scale_state,
    update_loss_scale, INITIAL_LOSS_SCALE, SCALE_WINDOW, DELAYED_SHIFT,
    MIN_LOSS_SCALE)
from deepspeed_tpu.runtime import lr_schedules
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
from deepspeed_tpu.runtime.prefetch import PrefetchLoader
from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop
from deepspeed_tpu.runtime import checkpoint as ckpt_io
from deepspeed_tpu.runtime.checkpoint import (save_checkpoint_files,
                                              load_checkpoint_files,
                                              read_latest_tag,
                                              validate_checkpoint_tag,
                                              write_latest_tag)
from deepspeed_tpu.utils.logging import logger, log_dist
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from deepspeed_tpu.monitor import (Monitor, SPAN_BACKWARD, SPAN_CKPT,
                                   SPAN_FORWARD, SPAN_STEP)

MEMORY_OPT_ALLREDUCE_SIZE = 500000000



class EngineState(NamedTuple):
    """All device-resident training state (a single pytree so the whole
    step can donate/alias buffers)."""
    params: Any        # compute-dtype params (model.apply consumes these)
    master: Any        # fp32 masters (None in pure-fp32 mode)
    opt_state: Any
    scale: LossScaleState
    acc_grads: Any     # fp32 cross-microbatch accumulator
    skipped: jnp.ndarray   # i32: overflow-skipped step count
    global_steps: jnp.ndarray  # i32


def _global_norm(tree):
    leaves = [jnp.vdot(x.astype(jnp.float32), x.astype(jnp.float32))
              for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def _batch_token_count(batch):
    """Token/element count of a batch, from the FIRST leaf's static
    shape — no device access. For token models ([.., b, t] int ids)
    this is the literal token count; for dense batches it is the
    element count of the primary input."""
    leaves = jax.tree_util.tree_leaves(batch)
    if not leaves:
        return 0
    return int(np.prod(np.shape(leaves[0])))


def _fetch_to_host(tree):
    """device_get that also handles multi-host (non-fully-addressable)
    sharded arrays by all-gathering them across processes first."""
    def one(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
        return jax.device_get(x)
    return jax.tree_util.tree_map(one, tree)


class DeepSpeedEngine(ZeroOffloadMixin):
    """TPU training engine.

    Args mirror `deepspeed.initialize` (ref `__init__.py:50`):
      model: an object with `.loss_fn(params, batch, rngs, deterministic)`
        (e.g. `models.gpt2.GPT2ForCausalLM`), or a flax Module whose
        `apply` returns a scalar loss, or a plain callable
        `loss = f(params, batch, rngs)`.
      model_parameters: the parameter pytree (the JAX analogue of
        `model.parameters()`).
      optimizer: optional optax.GradientTransformation (client optimizer);
        otherwise built from the config's "optimizer" block.
    """

    def __init__(self,
                 args=None,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 dist_init_required=None,
                 collate_fn=None,
                 config=None,
                 config_params=None,
                 dont_change_device=False,
                 mesh=None,
                 rng_seed=42):
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_data = training_data
        self.collate_fn = collate_fn
        self.mpu = mpu

        config = config if config is not None else config_params
        if config is None and args is not None and \
                hasattr(args, "deepspeed_config") and \
                args.deepspeed_config is not None:
            config = args.deepspeed_config
        assert config is not None, \
            "DeepSpeed requires --deepspeed_config or a config dict"

        from deepspeed_tpu.runtime.config_utils import load_config_dict
        config_dict = load_config_dict(config)
        self.mesh = mesh if mesh is not None else build_mesh(
            config_dict.get(C.MESH))
        # expert-parallel devices ARE data-parallel devices (the
        # DeepSpeed-MoE convention): the global batch divides over
        # every non-model axis, so an `expert` axis multiplies the
        # data-parallel world exactly like pipe does
        self.dp_world_size = self.mesh.shape[DATA_AXIS] * \
            self.mesh.shape[PIPE_AXIS] * expert_axis_size(self.mesh)
        self.mp_world_size = self.mesh.shape[MODEL_AXIS]

        self._config = DeepSpeedConfig(config_dict, mpu,
                                       world_size=self.dp_world_size)
        # numerics health (monitor/numerics.py): resolved BEFORE the
        # model so layer-exposing resolutions can tap boundaries into
        # the loss they build
        _mon_cfg = self._config.monitor_config
        self._numerics_on = bool(_mon_cfg.enabled and
                                 _mon_cfg.numerics_enabled)
        # set by layer-exposing model resolutions (PipelineModule):
        # same signature as _loss_fn but returns (loss, act_stats[L,3])
        self._loss_and_health_fn = None
        self._act_layer_names = None
        self._resolve_model(model, model_parameters)

        # ---- precision mode ----
        self.fp16_mode = self._config.fp16_enabled
        self.bf16_mode = self._config.bfloat16_enabled
        self.compute_dtype = (jnp.float16 if self.fp16_mode else
                              jnp.bfloat16 if self.bf16_mode else jnp.float32)
        # bf16 {"master_weights": false}: no fp32 master, bf16 Adam
        # moments, stochastic-rounded param writes
        # (runtime/bf16_optimizer.py) — 6 B/param of optimizer state
        # instead of mixed precision's 16 B/param.
        self.bf16_sr_mode = (self.bf16_mode and
                             not self._config.bfloat16_master_weights and
                             not (self.zero_optimization() and
                                  self.zero_cpu_offload()))
        if self.bf16_mode and not self._config.bfloat16_master_weights \
                and not self.bf16_sr_mode:
            logger.warning(
                'bf16 {"master_weights": false} is ignored together '
                "with cpu_offload — the offload path IS the master "
                "store (fp32 masters + moments in host RAM); remove "
                "one of the two settings")
        self.mixed_precision = (self.fp16_mode or self.bf16_mode) and \
            not self.bf16_sr_mode
        self.dynamic_loss_scale_enabled = self.fp16_mode and \
            self._config.loss_scale == 0

        # ---- timers / logging (before deepspeed_io, which uses them) ----
        # the timers fence on what the last step produced: a host clock
        # around async dispatch otherwise times the enqueue
        self.timers = SynchronizedWallClockTimer(sync_on=self._step_outputs)
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_micro_batch_size_per_gpu(),
            num_workers=self.dp_world_size,
            steps_per_output=self.steps_per_print(),
            sync_on=self._step_outputs)
        # ---- telemetry (deepspeed_tpu/monitor): device-side metric
        # accumulators drained at sync fences, pluggable sinks, step
        # tracing, stall watchdog. Every hot-path hook is one attribute
        # check when monitor.enabled is false.
        self.monitor = Monitor(self, self._config.monitor_config)

        self.training_dataloader = self.deepspeed_io(training_data) \
            if training_data is not None else None
        self.summary_writer = None
        if self.tensorboard_enabled() and jax.process_index() == 0:
            self.summary_writer = self.get_summary_writer()

        self.micro_steps = 0
        # Host-side mirror of the device step counter: used for print/log
        # gating so the hot loop never blocks on device_get (the device
        # counters remain authoritative for checkpointing).
        self._host_steps = 0
        # tokens (elements of the first batch leaf) consumed since the
        # last optimizer step — host int fed to the monitor's
        # device-side accumulator, no sync
        self._tokens_pending = 0
        self._offload_last_norm = None
        # async checkpointing: lazily-built jitted snapshot + writer
        self._ckpt_snapshot_jit = None
        self._ckpt_writer = None
        self._pending_grads = None
        self._pending_loss = None
        self._pending_acts = None
        self._pending_router = None
        self.losses = None

        if self.gradient_predivide_factor() != 1.0 or \
                self._config.prescale_gradients:
            # Pre/post-divide reorders the DP averaging to dodge fp16
            # overflow in NCCL rings (ref engine.py:1123-1135); here grads
            # accumulate in fp32 and GSPMD averages exactly, so the knobs
            # cannot change numerics.
            logger.warning(
                "prescale_gradients/gradient_predivide_factor are no-ops: "
                "gradients accumulate in fp32 under SPMD (exact averaging)")

        # ---- activation checkpointing (ref engine wires the JSON block
        # into deepspeed.checkpointing via configure, checkpointing.py:747)
        ac = self._config.activation_checkpointing_config
        if any([ac.partition_activations, ac.cpu_checkpointing,
                ac.contiguous_memory_optimization,
                ac.synchronize_checkpoint_boundary, ac.profile]):
            from deepspeed_tpu.runtime.activation_checkpointing import \
                checkpointing as ds_checkpointing
            ds_checkpointing.configure(
                mpu, deepspeed_config=self._config, mesh=self.mesh)

        # ---- progressive layer drop ----
        self.progressive_layer_drop = None
        if self.pld_enabled():
            self.progressive_layer_drop = ProgressiveLayerDrop(
                **{k: v for k, v in (self.pld_params() or {}).items()})

        # ---- optimizer + sharding + state ----
        self._rng = jax.random.PRNGKey(rng_seed)
        # cached device constant: the no-PLD keep_prob; building a fresh
        # scalar per step would put a tiny H2D transfer on the hot path
        self._keep_prob_one = jnp.asarray(1.0, jnp.float32)
        self._steps_per_sync = \
            self._config.async_dispatch_steps_per_sync or \
            self.steps_per_print()
        self._init_autotune()
        self._init_overlap()
        self._init_quantized_compute()
        self._init_moe()
        self._configure_optimizer()
        self._configure_lr_scheduler(lr_scheduler)
        self._init_state()
        self._build_step_fns()

        if self._config.dump_state:
            self._config.print("DeepSpeedEngine configuration")

    # ------------------------------------------------------------------
    # model resolution
    # ------------------------------------------------------------------
    def _resolve_model(self, model, model_parameters):
        assert model is not None, "deepspeed.initialize requires a model"
        self.module = model
        if hasattr(model, "loss_fn"):
            if hasattr(model, "bind_zero3_scheduler"):
                # The ZeRO-3 gather scheduler is bound around each
                # TRACE, not left on the model: several engines may
                # share one model object (ABCorrectnessChecker builds a
                # stage-3 primary AND a ZeRO-0 shadow on the same
                # model), and each trace must see ITS engine's
                # schedule — direct model.loss_fn calls outside an
                # engine stay unscheduled.
                raw_loss = model.loss_fn

                def _loss_with_sched(*a, **k):
                    model.bind_zero3_scheduler(
                        getattr(self, "zero3_scheduler", None))
                    try:
                        return raw_loss(*a, **k)
                    finally:
                        model.bind_zero3_scheduler(None)
                self._loss_fn = _loss_with_sched
            else:
                self._loss_fn = model.loss_fn
        elif hasattr(model, "apply"):  # bare flax module returning loss
            import inspect
            try:
                accepted = set(
                    inspect.signature(type(model).__call__).parameters)
            except (TypeError, ValueError):
                accepted = set()

            def _flax_loss(params, batch, rngs=None, deterministic=False,
                           **kwargs):
                kw = {k: v for k, v in kwargs.items() if k in accepted}
                if "deterministic" in accepted:
                    kw["deterministic"] = deterministic
                return model.apply({"params": params}, batch,
                                   rngs=rngs or {}, **kw)
            self._loss_fn = _flax_loss
        elif callable(model):
            import inspect
            try:
                accepted = set(inspect.signature(model).parameters)
            except (TypeError, ValueError):
                accepted = set()

            def _callable_loss(params, batch, rngs=None, deterministic=False,
                               **kwargs):
                kw = {k: v for k, v in kwargs.items() if k in accepted}
                if "deterministic" in accepted:
                    kw["deterministic"] = deterministic
                return model(params, batch, rngs, **kw)
            self._loss_fn = _callable_loss
        else:
            raise TypeError(f"cannot adapt model of type {type(model)}")

        if model_parameters is None and hasattr(model, "params"):
            model_parameters = model.params
        assert model_parameters is not None, \
            "model_parameters (the parameter pytree) is required"
        self._initial_params = model_parameters

    # ------------------------------------------------------------------
    # config accessors (parity with ref engine.py:204-398)
    # ------------------------------------------------------------------
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def zero_optimization(self):
        return self._config.zero_enabled

    def zero_optimization_stage(self):
        return self._config.zero_optimization_stage

    def zero_reduce_scatter(self):
        return self._config.zero_config.reduce_scatter

    def zero_overlap_comm(self):
        return self._config.zero_config.overlap_comm

    def zero_cpu_offload(self):
        return self._config.zero_config.cpu_offload

    def zero_offload_wire(self):
        """The zero_optimization.offload_wire block (compressed offload
        wire format; runtime/zero/offload.py)."""
        zc = self._config.zero_config
        return dict(grad_bits=zc.offload_wire_grad_bits,
                    param_bits=zc.offload_wire_param_bits,
                    warmup_steps=zc.offload_wire_warmup_steps)

    def zero_reduce_bucket_size(self):
        return self._config.zero_config.reduce_bucket_size

    def zero_allgather_bucket_size(self):
        return self._config.zero_config.allgather_bucket_size

    def zero_elastic_checkpoint(self):
        return self._config.zero_config.elastic_checkpoint

    def zero_load_from_fp32_weights(self):
        return self._config.zero_config.load_from_fp32_weights

    def fp16_enabled(self):
        return self._config.fp16_enabled

    def bfloat16_enabled(self):
        return self._config.bfloat16_enabled

    def amp_enabled(self):
        # ref engine.py amp path; on TPU amp maps to bf16 (config.py)
        return self._config.amp_enabled

    def amp_params(self):
        return self._config.amp_params

    def loss_scale(self):
        return float(jax.device_get(self.state.scale.loss_scale))

    def dynamic_loss_scale(self):
        return self.dynamic_loss_scale_enabled

    def initial_dynamic_scale(self):
        return self._config.initial_dynamic_scale

    def dynamic_loss_scale_args(self):
        return self._config.dynamic_loss_scale_args

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def allreduce_always_fp32(self):
        return self._config.allreduce_always_fp32

    def postscale_gradients(self):
        return not self._config.prescale_gradients

    def gradient_predivide_factor(self):
        return self._config.gradient_predivide_factor

    def sparse_gradients_enabled(self):
        return self._config.sparse_gradients_enabled

    def steps_per_print(self):
        return self._config.steps_per_print

    def async_dispatch_enabled(self):
        """Effective async-dispatch mode (the config flag, vetoed when a
        client lr_scheduler object or ZeRO-Offload forces sync)."""
        return self._async_dispatch

    def steps_per_sync(self):
        """Host<->device metrics-fence cadence in optimizer steps
        (async_dispatch.steps_per_sync, or steps_per_print when 0)."""
        return self._steps_per_sync

    def prefetch_depth(self):
        return self._config.async_dispatch_prefetch_depth

    def wall_clock_breakdown(self):
        return self._config.wall_clock_breakdown

    def memory_breakdown(self):
        return self._config.memory_breakdown

    def tensorboard_enabled(self):
        return self._config.tensorboard_enabled

    def tensorboard_output_path(self):
        return self._config.tensorboard_output_path

    def tensorboard_job_name(self):
        return self._config.tensorboard_job_name

    def optimizer_name(self):
        return self.client_optimizer.__class__.__name__ \
            if self.client_optimizer and not isinstance(
                self.client_optimizer, optax.GradientTransformation) \
            else self._config.optimizer_name

    def optimizer_params(self):
        return self._config.optimizer_params

    def optimizer_legacy_fusion(self):
        return self._config.optimizer_legacy_fusion

    def scheduler_name(self):
        return self._config.scheduler_name

    def scheduler_params(self):
        return self._config.scheduler_params

    def flops_profiler_enabled(self):
        return self._config.flops_profiler_config.enabled

    def flops_profiler_profile_step(self):
        return self._config.flops_profiler_config.profile_step

    def flops_profiler_module_depth(self):
        return self._config.flops_profiler_config.module_depth

    def flops_profiler_top_modules(self):
        return self._config.flops_profiler_config.top_modules

    def flops_profiler_detailed(self):
        return self._config.flops_profiler_config.detailed

    def pld_enabled(self):
        return self._config.pld_enabled

    def pld_params(self):
        return self._config.pld_params

    def pld_theta(self):
        return self.progressive_layer_drop.get_theta() \
            if self.progressive_layer_drop else 1.0

    def checkpoint_tag_validation_enabled(self):
        return self._config.checkpoint_tag_validation_enabled

    def checkpoint_tag_validation_fail(self):
        return self._config.checkpoint_tag_validation_fail

    def checkpoint_async_save(self):
        """checkpoint.async_save: save_checkpoint costs the train loop
        only a device snapshot; serialization runs on a writer thread."""
        return self._config.checkpoint_async_save

    def checkpoint_keep_last(self):
        return self._config.checkpoint_keep_last

    def checkpoint_writer_queue_depth(self):
        return self._config.checkpoint_writer_queue_depth

    def checkpoint_queue_policy(self):
        return self._config.checkpoint_queue_policy

    def elasticity_enabled(self):
        return self._config.elasticity_enabled

    _tb_fallback_warned = False

    def get_summary_writer(self, name="DeepSpeedJobName", base=None):
        """TensorBoard writer for the legacy `tensorboard` config block.
        Served by the native tfevents writer (monitor/tfevents.py) —
        no torch import anywhere on this path; the config keys
        (enabled/output_path/job_name) keep their reference meaning.
        Returns None (warn-once) only when the log dir is unusable."""
        if base is None:
            base = os.path.join(os.path.expanduser("~"), "tensorboard")
        if self.tensorboard_output_path():
            base_dir = self.tensorboard_output_path()
        else:
            base_dir = base
        log_dir = os.path.join(base_dir, self.tensorboard_job_name() or name)
        try:
            from deepspeed_tpu.monitor.tfevents import SummaryWriter
            return SummaryWriter(log_dir)
        except Exception:
            if not DeepSpeedEngine._tb_fallback_warned:
                DeepSpeedEngine._tb_fallback_warned = True
                logger.warning(
                    "tensorboard unavailable; scalar summaries are "
                    "disabled for this run", exc_info=True)
            return None

    # ------------------------------------------------------------------
    # optimizer construction (ref engine.py:544-630 selection matrix)
    # ------------------------------------------------------------------
    def _pure_data_mesh(self):
        """Stage-0 replicated params over a multi-device data-only mesh:
        the scope where per-leaf shard_map collectives (CSR sparse
        grads, 1-bit Adam's compressed allreduce) are legal — the same
        scope as the reference's non-ZeRO fallback path. An `expert`
        axis disqualifies the mesh: those shard_map programs name only
        the data axis (in_specs, pmean, worker counts), while batch
        rows shard over (data, expert) — running them would leave each
        expert replica redundantly recomputing its whole data slice."""
        return (self.zero_optimization_stage() == 0 and
                not self._offload_enabled() and
                self.mesh.shape[DATA_AXIS] > 1 and
                self.mesh.shape[MODEL_AXIS] == 1 and
                self.mesh.shape[PIPE_AXIS] == 1 and
                expert_axis_size(self.mesh) == 1)

    def _build_optimizer_transform(self):
        self._use_onebit_shardmap = False
        self._onebit_freeze_step = None
        if isinstance(self.client_optimizer, optax.GradientTransformation):
            # Client optax optimizer: wrap so lr can be injected if it
            # isn't already an inject_hyperparams transform.
            self._base_lr = None
            return self.client_optimizer

        name = (self._config.optimizer_name or C.ADAM_OPTIMIZER).lower()
        params = dict(self._config.optimizer_params or {})
        lr = params.get("lr", 1e-3)
        betas = params.get("betas", (0.9, 0.999))
        eps = params.get("eps", 1e-8)
        weight_decay = params.get("weight_decay", 0.0)
        self._base_lr = lr

        if self.bf16_sr_mode:
            # Master-less bf16: moments live in bf16, update math in
            # fp32, param write-back stochastically rounded
            # (runtime/bf16_optimizer.py). Adam/AdamW only — the other
            # optimizers keep the fp32-master path.
            if name not in (C.ADAM_OPTIMIZER, C.ADAMW_OPTIMIZER):
                raise ValueError(
                    f'bf16 {{"master_weights": false}} supports '
                    f"Adam/AdamW only (got {name!r}); drop the flag to "
                    "use the fp32-master path")
            from deepspeed_tpu.runtime.bf16_optimizer import adamw_bf16
            if weight_decay and not params.get("adam_w_mode", True) and \
                    name != C.ADAMW_OPTIMIZER:
                logger.warning(
                    "bf16 master_weights=false uses decoupled (AdamW) "
                    "weight decay; adam_w_mode=false is ignored")
            return adamw_bf16(learning_rate=lr, b1=betas[0], b2=betas[1],
                              eps=eps, weight_decay=weight_decay)

        if name == C.ONEBIT_ADAM_OPTIMIZER:
            # 1-bit Adam (ref onebit_adam.py:18): freeze_step warmup then
            # sign-compressed momentum with error feedback. On a
            # multi-device pure-data mesh the engine compiles TWO step
            # programs and switches at freeze_step — exactly the
            # reference's host-side `enable_backward_allreduce = False`
            # flip (ref onebit_adam.py:372): the warmup program carries
            # the dense GSPMD grad reduction, the compressed program
            # keeps grads local and communicates only bit-packed
            # momentum signs inside shard_map.
            from deepspeed_tpu.runtime.fp16.onebit_adam import onebit_adam
            freeze_step = params.get("freeze_step", 100)
            kw = dict(learning_rate=lr, b1=betas[0], b2=betas[1], eps=eps,
                      weight_decay=weight_decay, freeze_step=freeze_step)
            self._onebit_kwargs = kw
            self._onebit_freeze_step = freeze_step
            self._use_onebit_shardmap = self._pure_data_mesh()
            if self._use_onebit_shardmap:
                # worker_error is per-worker state: [dp] leading dim,
                # sharded over the data axis (see onebit_adam docstring)
                kw["num_workers"] = self.mesh.shape[DATA_AXIS]
                self._onebit_kwargs = kw
                return onebit_adam(**kw, static_phase="warmup")
            if self.mesh.shape[DATA_AXIS] > 1:
                logger.warning(
                    "OnebitAdam compressed collective unavailable here "
                    "(needs zero stage 0, no offload, and a pure-data "
                    "mesh); falling back to the single-worker numerics "
                    "form with dense gradient reduction")
            return onebit_adam(**kw)
        if name in (C.ADAM_OPTIMIZER, C.ADAMW_OPTIMIZER):
            # FusedAdam defaults to adam_w_mode (ref ops/adam/fused_adam.py);
            # decoupled weight decay is the TPU-native choice too.
            adam_w_mode = params.get("adam_w_mode", True) or \
                name == C.ADAMW_OPTIMIZER
            if adam_w_mode:
                return optax.inject_hyperparams(optax.adamw)(
                    learning_rate=lr, b1=betas[0], b2=betas[1], eps=eps,
                    weight_decay=weight_decay)
            return optax.inject_hyperparams(optax.adam)(
                learning_rate=lr, b1=betas[0], b2=betas[1], eps=eps)
        if name == C.LAMB_OPTIMIZER:
            # reference-parity LAMB (clipped trust ratio, ref
            # csrc/lamb/fused_lamb_cuda_kernel.cu:279-306) — optax.lamb
            # never clips the coefficient
            from deepspeed_tpu.ops.lamb.fused_lamb import lamb as ds_lamb
            return ds_lamb(
                learning_rate=lr, b1=betas[0], b2=betas[1], eps=eps,
                weight_decay=weight_decay,
                max_coeff=params.get("max_coeff", 10.0),
                min_coeff=params.get("min_coeff", 0.01),
                bias_correction=params.get("bias_correction", True))
        if name == C.SGD_OPTIMIZER:
            momentum = params.get("momentum", 0.0)
            return optax.inject_hyperparams(optax.sgd)(
                learning_rate=lr, momentum=momentum or None)
        raise ValueError(f"Unknown optimizer {name}")

    def _configure_optimizer(self):
        self.optimizer_transform = self._build_optimizer_transform()
        # scheduler-facing shim mirroring torch param_groups
        self._optimizer_shim = lr_schedules._OptimizerShim(
            lr=self._base_lr or 0.0)
        self.optimizer = self  # `engine.optimizer` parity: exposes state

    def _configure_lr_scheduler(self, client_lr_scheduler):
        # Async dispatch needs a schedule it can compile into the step:
        # a client scheduler object is arbitrary host code (sync mode),
        # and ZeRO-Offload's host optimizer step is a sync by nature.
        self._device_lr_fn = None
        self._async_dispatch = (self._config.async_dispatch_enabled and
                                client_lr_scheduler is None and
                                not self._offload_enabled())
        if client_lr_scheduler is not None:
            self.lr_scheduler = client_lr_scheduler
            if self._config.async_dispatch_enabled:
                log_dist(
                    "async_dispatch: disabled — a client lr_scheduler "
                    "object cannot be compiled into the jitted step "
                    "(use the config scheduler block for the sync-free "
                    "hot path)", ranks=[0])
            return
        name = self.scheduler_name()
        if name is None:
            self.lr_scheduler = None
            self._device_lr_fn = lr_schedules.device_schedule_fn(
                None, base_lr=self._base_lr)
            return
        sched_cls = {
            lr_schedules.LR_RANGE_TEST: lr_schedules.LRRangeTest,
            lr_schedules.ONE_CYCLE: lr_schedules.OneCycle,
            lr_schedules.WARMUP_LR: lr_schedules.WarmupLR,
            lr_schedules.WARMUP_DECAY_LR: lr_schedules.WarmupDecayLR,
        }.get(name)
        if sched_cls is None:
            raise ValueError(f"Unknown scheduler {name}")
        params = self.scheduler_params() or {}
        self.lr_scheduler = sched_cls(self._optimizer_shim, **params)
        self._device_lr_fn = lr_schedules.device_schedule_fn(name, params)
        log_dist(f"Using LR scheduler {name}"
                 + (" (device-resident under async dispatch)"
                    if self._async_dispatch else ""), ranks=[0])

    def _current_lr(self):
        if self.lr_scheduler is not None:
            try:
                return float(self.lr_scheduler.get_last_lr()[0])
            except AssertionError:
                lrs = self.lr_scheduler.get_lr()
                return float(lrs[0])
        return float(self._base_lr if self._base_lr is not None else 0.0)

    def get_lr(self):
        # Under async fp16 the host scheduler is an optimistic mirror;
        # an explicit lr query is a user-initiated sync point (like
        # loss_scale()), so refresh it first.
        self._sync_scheduler_mirror()
        return [self._current_lr()]

    def get_mom(self):
        if self.lr_scheduler is not None and \
                hasattr(self.lr_scheduler, "get_mom"):
            mom = self.lr_scheduler.get_mom()
            if mom is not None:
                return mom
        return [self._optimizer_shim.param_groups[0].get("betas",
                                                         (0.9, 0.999))]

    # ------------------------------------------------------------------
    # state init + sharding
    # ------------------------------------------------------------------
    def _scalars_on_mesh(self, state):
        """The scalar leaves of a freshly built state, placed on the
        mesh replicated — the type the step returns them with. Born as
        plain single-device scalars they change type after the first
        step, and the whole train step traces and compiles a second
        time."""
        scale, skipped, global_steps = jax.device_put(
            (state.scale, state.skipped, state.global_steps),
            NamedSharding(self.mesh, PartitionSpec()))
        return state._replace(scale=scale, skipped=skipped,
                              global_steps=global_steps)

    def _init_state(self):
        # Copy jax arrays: device_put of an already-placed array aliases
        # it, and the step donates its input state — without the copy the
        # caller's (possibly shared) initial params would be invalidated
        # after the first step.
        # On-device state is cast straight from the caller's tree by
        # jitted programs whose outputs are born with their shardings,
        # so the fp32 tree stays ABSTRACT: a concrete fp32 copy lands
        # whole on the default device (6.2 GB at 1.5B params) next to
        # the real state — enough to OOM that one device while the rest
        # of the mesh holds only its shard. Only the host-side offload
        # store and plain fp32 training consume concrete fp32 values.
        born_sharded = self.bf16_sr_mode or (
            self.mixed_precision and not self._offload_enabled())
        if born_sharded:
            params_f32 = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.float32),
                self._initial_params)
        else:
            params_f32 = jax.tree_util.tree_map(
                lambda x: jnp.array(x, dtype=jnp.float32, copy=True)
                if isinstance(x, jax.Array)
                else jnp.asarray(x, jnp.float32), self._initial_params)

        tp_specs = None
        specs_override = getattr(self, "_param_specs_override", None)
        if specs_override is not None:
            # PipelineEngine's per-stage flat layout: flat buffers carry
            # a pipe-axis spec, tied leaves replicate
            tp_specs = specs_override(params_f32)
        elif hasattr(self.module, "tp_param_specs"):
            # TP (and, for pipelined models, pipe-stage) placement; a
            # spec naming a size-1 mesh axis is a no-op, so this is safe
            # for pure-DP meshes too.
            tp_specs = self.module.tp_param_specs(params_f32)
        # _zero_stage_cap: the flat-stage pipe layout already partitions
        # parameters (over pipe); stage-3 data-axis param sharding on
        # top would break the interpreter's local-slice invariant
        effective_stage = min(self.zero_optimization_stage(),
                              getattr(self, "_zero_stage_cap", 3))
        if effective_stage != self.zero_optimization_stage():
            logger.warning(
                f"ZeRO stage {self.zero_optimization_stage()} is capped "
                f"to {effective_stage} under the pipeline's per-stage "
                "flat parameter layout: parameters are already "
                "partitioned over the pipe axis; optimizer state / "
                "gradients still shard over the data axis")
        self.zero_policy = ZeroShardingPolicy(
            self.mesh, effective_stage, param_specs=tp_specs)

        self._param_shardings = self.zero_policy.param_shardings(params_f32)

        # Leaves with no dp-divisible dim are stored PADDED in the
        # sharded state groups (master/moments/grad-accum) so they truly
        # shard instead of silently replicating — the TPU-native form of
        # the reference's sub-partition alignment (ref stage1.py:198-261).
        # Compute-dtype params keep true shapes; padding is sliced off
        # after each update and on checkpoint save.
        self._zero_pad_plan = {}
        # SR mode shards its bf16 Adam moments (and gas>1 fp32
        # accumulator) over the data axis exactly like the fp32-master
        # path, so it needs the same padding for non-divisible leaves.
        if (self.mixed_precision or self.bf16_sr_mode) and \
                not self._offload_enabled():
            self._zero_pad_plan = self.zero_policy.pad_plan(params_f32)
            if self._zero_pad_plan:
                log_dist(
                    f"ZeRO: padding {len(self._zero_pad_plan)} "
                    "non-divisible leaves for data-axis sharding",
                    ranks=[0])
        params_enc = self.zero_policy.encode(params_f32,
                                             self._zero_pad_plan)
        self._master_shardings = self.zero_policy.master_shardings(params_enc)
        self._acc_shardings = self.zero_policy.grad_accum_shardings(params_enc)
        # shapes only: every consumer builds zeros or reads paths
        self._params_enc_template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.float32),
            params_enc)
        self._init_zero3_scheduler(effective_stage)

        def cast_tree(dtype):
            return lambda t: jax.tree_util.tree_map(
                lambda x: jnp.asarray(x, dtype), t)

        if born_sharded:
            # jitted with out_shardings: outputs are fresh buffers (the
            # donation contract the old copy=True provided) AND born
            # sharded, so no unsharded cast tree transits HBM/RAM
            # (25 GB at 13B).
            params = jax.jit(
                cast_tree(self.compute_dtype),
                out_shardings=self._param_shardings)(self._initial_params)
            master = None
            if self.mixed_precision:
                master = jax.jit(
                    lambda t: self.zero_policy.encode(
                        cast_tree(jnp.float32)(t), self._zero_pad_plan),
                    out_shardings=self._master_shardings)(
                        self._initial_params)
        elif self._offload_enabled():
            # the fp32 master stays in host RAM (_init_offload below)
            params = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(
                    jnp.asarray(x, self.compute_dtype), s),
                params_f32, self._param_shardings)
            master = None
        else:
            master = None
            params = jax.device_put(params_f32, self._param_shardings)

        if self._offload_enabled():
            # ZeRO-Offload: no device master/opt state; host-side fp32
            # masters + CPU-Adam moments (runtime/zero/offload.py)
            self._init_offload(params_f32)
            self.state = self._scalars_on_mesh(EngineState(
                params=params, master=None, opt_state=(),
                scale=make_static_loss_scale_state(
                    self._host_scaler.cur_scale),
                acc_grads=jax.device_put(_zeros_like_f32(params_f32),
                                         self._acc_shardings),
                skipped=jnp.asarray(0, jnp.int32),
                global_steps=jnp.asarray(0, jnp.int32)))
            n_params = sum(np.prod(l.shape) for l in
                           jax.tree_util.tree_leaves(params_f32))
            log_dist(
                f"engine initialized (offload): {n_params/1e6:.1f}M params, "
                f"zero_stage={self.zero_optimization_stage()}, "
                f"dtype={self.compute_dtype.__name__}, "
                f"mesh={dict(self.mesh.shape)}", ranks=[0])
            self._register_memory_ledger()
            self._initial_params = None   # don't pin the caller's copy
            return

        if self.mixed_precision:
            opt_target = master
        elif self.bf16_sr_mode and self._zero_pad_plan:
            # moments live in the padded (encoded) layout so they truly
            # shard; params themselves keep true shapes for the model
            opt_target = self.zero_policy.encode(params,
                                                 self._zero_pad_plan)
        else:
            opt_target = params
        # Shardings are computed from ABSTRACT shapes and the init runs
        # jitted with out_shardings, so moments are born sharded — an
        # eager init would materialize the full unsharded moment tree
        # (100+ GB at 13B) on one device before resharding.
        opt_shape = jax.eval_shape(self.optimizer_transform.init,
                                   opt_target)
        if self.lr_scheduler is not None and \
                "learning_rate" not in getattr(opt_shape, "hyperparams", {}):
            logger.warning(
                "an LR scheduler is configured but the client optimizer "
                "exposes no injectable 'learning_rate' hyperparam "
                "(wrap it with optax.inject_hyperparams); scheduler values "
                "will not be applied")
        self._opt_shardings = self.zero_policy.opt_state_shardings(
            opt_shape, self._params_enc_template)
        if self._use_onebit_shardmap:
            self._opt_shardings = self._opt_shardings._replace(
                worker_error=jax.tree_util.tree_map(
                    lambda w: NamedSharding(
                        self.mesh,
                        PartitionSpec(DATA_AXIS,
                                      *([None] * (w.ndim - 1)))),
                    opt_shape.worker_error))
        opt_state = jax.jit(
            self.optimizer_transform.init,
            out_shardings=self._opt_shardings)(opt_target)

        if self.fp16_mode:
            if self.dynamic_loss_scale_enabled:
                args = self.dynamic_loss_scale_args() or {}
                scale = make_loss_scale_state(
                    init_scale=args.get(INITIAL_LOSS_SCALE,
                                        self.initial_dynamic_scale()),
                    delayed_shift=args.get(DELAYED_SHIFT, 2))
            else:
                scale = make_static_loss_scale_state(self._config.loss_scale)
        else:
            scale = make_static_loss_scale_state(1.0)

        # With no gradient accumulation the persistent fp32 accumulator
        # is pure overhead (equal in size to the master weights); grads
        # flow straight from the microbatch into the update instead.
        if self._jit_gas() == 1:
            acc = ()
        else:
            acc = jax.device_put(_zeros_like_f32(self._params_enc_template),
                                 self._acc_shardings)

        self.state = self._scalars_on_mesh(EngineState(
            params=params, master=master, opt_state=opt_state, scale=scale,
            acc_grads=acc,
            skipped=jnp.asarray(0, jnp.int32),
            global_steps=jnp.asarray(0, jnp.int32)))

        n_params = self._count_model_params(params_f32)
        # cached for the monitor's in-loop MFU derivation (6·N·tokens/s
        # against the chip's nominal peak — the conservative convention)
        self._n_model_params = n_params
        log_dist(
            f"engine initialized: {n_params/1e6:.1f}M params, "
            f"zero_stage={self.zero_policy.stage}, "
            f"dtype={self.compute_dtype.__name__}, "
            f"mesh={dict(self.mesh.shape)}", ranks=[0])
        if self._numerics_on:
            # host-side labels for the numerics stat rows: grad groups
            # from the encoded-layout template (the tree the jitted
            # stats walk), activation boundaries from the resolver
            from deepspeed_tpu.monitor import numerics as _num
            self.monitor.set_numerics_labels(
                grad=_num.group_paths(self._params_enc_template),
                act=self._act_layer_names)
        self._register_memory_ledger()
        self._initial_params = None   # don't pin the caller's copy

    def _init_autotune(self):
        """Wire the kernel block-size autotuner (ops/autotune.py):
        apply the `autotune` config block (enabled toggle + table
        path) and attach the monitor so `autotune_search` /
        `autotune_hit` events flow to the sinks. Lookups then happen
        transparently inside the kernel entry points at trace time —
        pure host-side dict reads, no device sync."""
        from deepspeed_tpu.ops import autotune
        at = self._config.autotune
        autotune.configure(
            enabled=at["enabled"],
            table_path=at["table_path"],
            monitor=self.monitor if self.monitor.enabled else False)

    def _init_overlap(self):
        """Wire the `overlap` config block into the shared
        communication/compute overlap runtime (ops/overlap.py):
        enabled toggle, pinned-vs-autotuned site set, and the default
        issue distance. Emits one `overlap` monitor event recording
        the configuration. Schedule resolution afterwards is a pure
        host-side dict read at trace time — no device sync."""
        from deepspeed_tpu.ops import overlap
        ov = self._config.overlap
        overlap.configure(
            enabled=ov["enabled"],
            sites=ov["sites"],
            issue_distance=ov["issue_distance"])
        if self.monitor.enabled:
            self.monitor.event(
                "overlap", enabled=ov["enabled"],
                sites=(ov["sites"] if isinstance(ov["sites"], str)
                       else ",".join(sorted(ov["sites"]))),
                issue_distance=ov["issue_distance"])

    def _init_quantized_compute(self):
        """Wire the `quantized_compute` config block into the model:
        call its `configure_quantized_compute` hook (GPT-2 family)
        with the configured mode/block/stochastic_rounding, emit one
        `quantized_matmul` monitor event recording the configuration,
        and warn when the model does not expose the hook (the config
        then has no effect on this model)."""
        qc = self._config.quantized_compute
        if not qc["enabled"]:
            return
        target = getattr(self, "module", None)
        hook = getattr(target, "configure_quantized_compute", None)
        if hook is None:
            logger.warning(
                "quantized_compute.enabled is set but the model "
                f"({type(target).__name__}) exposes no "
                "configure_quantized_compute hook; forward matmuls "
                "stay unquantized")
            applied = False
        else:
            hook(qc["mode"], block=qc["block"],
                 stochastic_rounding=qc["stochastic_rounding"])
            applied = True
        if self.monitor.enabled:
            from deepspeed_tpu.ops.transformer.quantized_matmul \
                import resolve_quantized_compute
            self.monitor.event(
                "quantized_matmul", applied=applied,
                mode=qc["mode"], block=qc["block"],
                stochastic_rounding=qc["stochastic_rounding"],
                active=bool(applied and
                            resolve_quantized_compute(qc["mode"])))

    def _init_moe(self):
        """Wire the `moe` config block into the model
        (deepspeed_tpu/moe/): validate the expert mesh axis against the
        expert count, call the model's `configure_moe` hook with the
        engine mesh + router knobs (structural keys are VERIFIED
        against the built parameter tree, router knobs applied), and
        emit one `moe` monitor event recording the configuration.
        Runs BEFORE state init so `tp_param_specs` sees the expert
        placement when the ZeRO policy is built."""
        mc = self._config.moe
        self._moe_active = False
        self._moe_stats_on = False
        if not mc["enabled"]:
            return
        target = getattr(self, "module", None)
        hook = getattr(target, "configure_moe", None)
        if hook is None:
            logger.warning(
                "moe.enabled is set but the model "
                f"({type(target).__name__}) exposes no configure_moe "
                "hook; the moe block has no effect on this model")
            return
        es = expert_axis_size(self.mesh)
        if mc["num_experts"] % es:
            raise ValueError(
                f"moe.num_experts={mc['num_experts']} must divide by "
                f"the mesh expert axis ({es}): each expert-parallel "
                "device group owns num_experts/expert contiguous "
                "experts")
        hook(mesh=self.mesh,
             num_experts=mc["num_experts"],
             every_n_layers=mc["every_n_layers"],
             top_k=mc["top_k"],
             capacity_factor=mc["capacity_factor"],
             aux_loss_weight=mc["aux_loss_weight"],
             jitter_eps=mc["jitter_eps"],
             fused_dispatch=mc["fused_dispatch"])
        self._moe_active = True
        # router stats ride the jitted step only when something drains
        # them (the monitor fence) — dense-engine traces stay identical
        self._moe_stats_on = self.monitor.enabled
        if self.monitor.enabled:
            self.monitor.event(
                "moe", num_experts=mc["num_experts"],
                top_k=mc["top_k"],
                capacity_factor=mc["capacity_factor"],
                aux_loss_weight=mc["aux_loss_weight"],
                every_n_layers=mc["every_n_layers"],
                jitter_eps=mc["jitter_eps"],
                fused_dispatch=mc["fused_dispatch"],
                expert_axis=es)
        log_dist(
            f"MoE: {mc['num_experts']} experts (top_k={mc['top_k']}, "
            f"cf={mc['capacity_factor']}, every_n_layers="
            f"{mc['every_n_layers']}) over expert axis {es}",
            ranks=[0])

    def _init_zero3_scheduler(self, effective_stage):
        """Build + bind the explicit ZeRO-3 gather/release runtime
        (runtime/zero/stage3.py): layer-granular all-gather prefetched
        `prefetch_layers` ahead of use, released after its fwd/bwd use,
        gradients reduce-scattered into the owning data-axis shard.
        Weaves through models exposing `bind_zero3_scheduler` (GPT-2 /
        BERT layer stacks) or the sequential PipelineModule chain;
        everything else keeps the implicit-GSPMD stage-3 behavior
        (params sharded, XLA chooses where to materialize)."""
        self.zero3_scheduler = None
        zc = self._config.zero_config
        if effective_stage != 3 or not zc.stage3_enabled:
            return
        if self.mesh.shape[MODEL_AXIS] > 1:
            logger.warning(
                "ZeRO-3 gather scheduler: disabled on a model-parallel "
                "mesh (the scheduled gather replicates over ALL "
                "non-data axes, which would undo tensor-parallel "
                "placement); stage-3 params stay sharded with "
                "XLA-implicit gathers")
            return
        if self.progressive_layer_drop is not None:
            logger.warning(
                "ZeRO-3 gather scheduler: disabled with "
                "progressive_layer_drop (the scheduled stack has no "
                "per-layer keep-prob gate); stage-3 params stay "
                "sharded with XLA-implicit gathers")
            return
        from deepspeed_tpu.runtime.zero.stage3 import Zero3GatherScheduler
        s3 = self.zero_stage3_config()
        sched = Zero3GatherScheduler(
            self.mesh,
            prefetch_layers=s3["prefetch_layers"],
            release_after_use=s3["release_after_use"],
            gather_dtype=s3["gather_dtype"])
        if not hasattr(self.module, "bind_zero3_scheduler") and \
                not getattr(self, "_zero3_chain_capable", False):
            log_dist(
                "ZeRO-3: model exposes no layer-stack hook "
                "(bind_zero3_scheduler) and is not a sequential "
                "PipelineModule chain; params stay sharded with "
                "XLA-implicit gathers (no gather scheduling control)",
                ranks=[0])
            return
        self.zero3_scheduler = sched
        log_dist(
            "ZeRO-3 runtime: gather/release scheduler on "
            f"(prefetch_layers={sched.prefetch_layers}, "
            f"release_after_use={sched.release_after_use}, "
            f"gather_dtype={zc.stage3_gather_dtype}) — live full-param "
            f"bytes bounded by {sched.prefetch_layers + 1} layers"
            if sched.release_after_use else
            "ZeRO-3 runtime: NAIVE up-front gather "
            "(stage3.release_after_use=false) — the whole param stack "
            "is gathered at step start and held live; this is the "
            "A/B baseline, not a memory-bounded mode", ranks=[0])

    def zero_stage3_config(self):
        """The zero_optimization.stage3 block (explicit stage-3
        gather/release runtime; runtime/zero/stage3.py)."""
        zc = self._config.zero_config
        return dict(enabled=zc.stage3_enabled,
                    prefetch_layers=zc.stage3_prefetch_layers,
                    release_after_use=zc.stage3_release_after_use,
                    gather_dtype=zc.stage3_gather_dtype)

    def _register_memory_ledger(self):
        """Register the engine's long-lived device state groups with
        the monitor's memory ledger (monitor/memory.py). Init-time
        shape/sharding metadata only — per-device bytes come from
        `sharding.shard_shape`, so ZeRO-sharded groups register what
        ONE device actually holds. Runs unconditionally (the ledger is
        a dict; there is no per-step cost)."""
        from deepspeed_tpu.monitor import memory as _mem
        led = self.monitor.ledger
        st = self.state
        led.register_tree(_mem.CAT_PARAMS, "engine.params", st.params)
        if st.master is not None:
            led.register_tree(_mem.CAT_MASTER, "engine.master_fp32",
                              st.master)
        if st.opt_state:
            led.register_tree(_mem.CAT_OPT, "engine.opt_state",
                              st.opt_state)
        if st.acc_grads:
            led.register_tree(_mem.CAT_GRADS, "engine.acc_grads",
                              st.acc_grads)
        if getattr(self, "zero3_scheduler", None) is not None:
            # stage-3 gathered-param prefetch window: a DYNAMIC entry —
            # the scheduler learns its per-layer bytes when the first
            # step traces, and the ledger samples it at each fence, so
            # OOM forensics can name stage3.prefetch_layers as the knob
            led.register_dynamic(
                _mem.CAT_ZERO3, "zero3.gather_window",
                self.zero3_scheduler.live_window_bytes)
        if getattr(self, "_moe_active", False):
            # MoE all-to-all dispatch buffers: the [E, C, H] send +
            # expert-output recv pair per MoE layer — per-layer bytes
            # learned at trace time (deepspeed_tpu/moe/dispatch.py),
            # times the model's MoE layer count. DYNAMIC like
            # zero3_gather: 0 until the first step traces; OOM
            # forensics can then name moe.capacity_factor as the knob
            from deepspeed_tpu.moe.dispatch import \
                dispatch_bytes_per_layer
            info = getattr(self.module, "moe_info", lambda: None)()
            n_moe_layers = int((info or {}).get("moe_layers", 1))
            n_experts = (info or {}).get("num_experts")
            width = (info or {}).get("width")
            mesh = self.mesh
            led.register_dynamic(
                _mem.CAT_MOE, "moe.dispatch_buffers",
                lambda: dispatch_bytes_per_layer(
                    mesh, num_experts=n_experts,
                    width=width) * n_moe_layers)
        # comm/compute overlap in-flight staging (MoE dispatch window,
        # ring send/recv rotations): per-device bytes registered by
        # the sites at trace time (ops/overlap.py record_inflight) —
        # DYNAMIC like zero3_gather: 0 until the first step traces and
        # 0 whenever every site resolves to overlap-off; OOM forensics
        # can then name overlap.issue_distance as the knob
        from deepspeed_tpu.ops import overlap as _overlap
        led.register_dynamic(
            _mem.CAT_OVERLAP, "overlap.inflight_window",
            _overlap.inflight_bytes)

    def _count_model_params(self, tree):
        """Model parameter count for logs/profiling; engines whose
        stored layout carries padding override this."""
        return sum(int(np.prod(l.shape)) for l in
                   jax.tree_util.tree_leaves(tree))

    # ------------------------------------------------------------------
    # jitted step functions
    # ------------------------------------------------------------------
    def _scaled_loss_fn(self, params, batch, rng, loss_scale, keep_prob):
        """Returns (scaled_loss, (raw_loss, act_stats, router_stats)).
        act_stats is None unless numerics health is on AND the model
        resolution provided a boundary-tapping loss
        (`_loss_and_health_fn`); router_stats ([E+2] device vector —
        per-expert load, drop fraction, aux loss) is None unless an
        MoE model is wired AND the monitor drains it at fences."""
        gas = self._jit_gas()
        # "quant" is the per-step stream the quantized-compute family's
        # stochastic rounding consumes (decorrelated from dropout by the
        # fold; models without quantized modules never draw from it)
        rngs = {"dropout": rng, "params": rng,
                "quant": jax.random.fold_in(rng, 0x51)}
        kwargs = {}
        if self.progressive_layer_drop is not None:
            kwargs["layer_keep_prob"] = keep_prob
        rstats = None
        if self._numerics_on and self._loss_and_health_fn is not None:
            loss, acts = self._loss_and_health_fn(
                params, batch, rngs=rngs, deterministic=False, **kwargs)
        elif self._moe_stats_on:
            # the stats already live in the traced loss graph (the aux
            # term consumes them) — returning them adds no compute,
            # and they stay device-side until the monitor fence
            loss, rstats = self._loss_fn(
                params, batch, rngs=rngs, deterministic=False,
                return_router_stats=True, **kwargs)
            acts = None
        else:
            loss = self._loss_fn(params, batch, rngs=rngs,
                                 deterministic=False, **kwargs)
            acts = None
        return loss * (loss_scale / gas), (loss, acts, rstats)

    def _micro_grad(self, params, batch, rng, loss_scale, keep_prob):
        """(raw_loss, grads, act_stats, router_stats) for one
        microbatch; act_stats is None unless numerics activation
        tapping is active, router_stats unless MoE stats are on."""
        if self._use_shardmap_grads:
            loss, grads = self._micro_grad_shardmap(params, batch, rng,
                                                    loss_scale, keep_prob)
            return loss, grads, None, None
        grad_fn = jax.value_and_grad(self._scaled_loss_fn, has_aux=True)
        (_, (raw_loss, acts, rstats)), grads = grad_fn(
            params, batch, rng, loss_scale, keep_prob)
        if not (self.bf16_sr_mode and self._jit_gas() == 1):
            # fp32 grads for accumulation / the fp32-master update. In
            # SR mode at gas=1 they stay in compute dtype: the update
            # math casts per-leaf inside its fused elementwise chain,
            # and a whole-tree fp32 cast here would MATERIALIZE a
            # params-sized fp32 tree (6.2 GB at 1.5B) at peak memory.
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
        # pad-plan leaves: grads join the encoded (padded) layout here so
        # accumulator/master/update shapes all agree; padding is zeros
        grads = self.zero_policy.encode(grads, self._zero_pad_plan)
        grads = jax.lax.with_sharding_constraint(
            grads, self._acc_shardings)
        return raw_loss, grads, acts, rstats

    def _sparse_grad_paths(self):
        if not self.sparse_gradients_enabled():
            return ()
        return tuple(getattr(self.module, "sparse_grad_paths",
                             lambda: ())())

    def _micro_grad_shardmap(self, params, batch, rng, loss_scale,
                             keep_prob):
        """Gradients via an explicit shard_map over the data axis, so
        per-leaf collectives can diverge from dense psum: embedding
        grads ride the CSR all-gather (ref `engine.py:1190-1246`) and
        1-bit Adam's compressed allreduce gets a real axis to run over.
        Only used at ZeRO stage 0 (params replicated), matching the
        reference, whose CSR path lives in the non-ZeRO fallback
        (`engine.py:836,1160`)."""
        from jax import shard_map
        from deepspeed_tpu.runtime.csr_tensor import csr_mean_rows

        sparse_paths = self._sparse_grad_paths()
        mesh = self.mesh

        kp_is_none = keep_prob is None

        def per_shard(params, batch, rng, loss_scale, kp):
            kp = None if kp_is_none else kp
            rng = jax.random.fold_in(
                rng, jax.lax.axis_index(DATA_AXIS))
            grad_fn = jax.value_and_grad(self._scaled_loss_fn,
                                         has_aux=True)
            # act/router stats are dropped on the CSR shard_map path
            # (its out_specs predate numerics health; stage-0 sparse
            # models still get grad-group stats from the update tail)
            (_, (raw_loss, _acts, _rstats)), grads = grad_fn(
                params, batch, rng, loss_scale, kp)
            tokens = int(np.prod(
                jax.tree_util.tree_leaves(batch)[0].shape))

            flat = jax.tree_util.tree_flatten_with_path(grads)
            leaves = []
            for path, g in flat[0]:
                key = jax.tree_util.keystr(path)
                g = g.astype(jnp.float32)
                if any(p in key for p in sparse_paths) and g.ndim == 2:
                    capacity = min(g.shape[0], tokens)
                    g = csr_mean_rows(g, DATA_AXIS, capacity)
                else:
                    g = jax.lax.pmean(g, DATA_AXIS)
                leaves.append(g)
            grads = jax.tree_util.tree_unflatten(flat[1], leaves)
            return jax.lax.pmean(raw_loss, DATA_AXIS), grads

        P = PartitionSpec

        def batch_spec(x):
            return P(DATA_AXIS, *([None] * (x.ndim - 1)))

        batch_specs = jax.tree_util.tree_map(batch_spec, batch)
        kp_in = jnp.float32(0.0) if kp_is_none else keep_prob
        raw_loss, grads = shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(), batch_specs, P(), P(), P()),
            out_specs=(P(), P()),
            check_vma=False)(params, batch, rng, loss_scale, kp_in)
        return raw_loss, grads

    def _unscale_clip_and_update(self, state: EngineState, lr,
                                 grads=None, transform=None,
                                 local_axis=None, with_health=True):
        """Tail of the step: unscale, overflow vote, clip, cond-update.
        `grads` (gas=1 fast path) bypasses the persistent accumulator.
        `transform` overrides self.optimizer_transform (1-bit Adam's
        compressed-phase program). `local_axis`: set when running
        per-shard inside shard_map with LOCAL grads — the norm becomes
        sqrt(psum(|g_w|^2)/W) (exact when shards agree, conservative
        otherwise, and continuous with the warmup path's global norm at
        the phase transition), the clip factor derived from it is
        identical on every worker, and sharding constraints (illegal
        inside shard_map) are skipped."""
        if transform is None:
            transform = self.optimizer_transform
        scale = state.scale.loss_scale
        grads = grads if grads is not None else state.acc_grads
        if self.fp16_mode:
            grads = jax.tree_util.tree_map(lambda g: g / scale, grads)
        # else: scale is statically 1.0 — dividing by the traced fp32
        # scalar would type-promote every bf16 grad leaf to fp32 with two
        # consumers (norm + update), letting XLA materialize a full fp32
        # grad tree at peak in SR gas=1 mode
        clip = self.gradient_clipping()
        if self._numerics_on and with_health:
            # per-group numerics health on the UNSCALED grads (norm /
            # absmax / nonfinite flag per top-level group — the
            # overflow source). The per-leaf sum-of-squares pass is
            # computed ONCE and shared with the global norm below, so
            # with clipping/fp16 the accumulators add exactly one new
            # reduction pass (absmax) per leaf to the jitted step
            from deepspeed_tpu.monitor import numerics as _num
            sq_tree = _num.leaf_sumsq(grads)
            health_grad = _num.grad_group_stats(grads, sq_tree=sq_tree)
        else:
            sq_tree = None
            health_grad = None
        if self.fp16_mode or (clip and clip > 0):
            grad_norm = jnp.sqrt(jnp.sum(jnp.stack(
                jax.tree_util.tree_leaves(sq_tree)))) \
                if sq_tree is not None else _global_norm(grads)
        else:
            # nothing consumes the norm (no overflow vote off-fp16, no
            # clip): computing it anyway costs a full extra HBM read of
            # the grad tree (~3 GB at 1.5B) purely for logging
            grad_norm = jnp.float32(0.0)
        if local_axis is not None:
            w = self.mesh.shape[local_axis]
            grad_norm = jnp.sqrt(
                jax.lax.psum(grad_norm * grad_norm, local_axis) / w)
        if self.fp16_mode:
            overflow = ~jnp.isfinite(grad_norm)
        else:
            overflow = jnp.asarray(False)

        if clip and clip > 0:
            factor = jnp.minimum(1.0, clip / (grad_norm + 1e-6))
            factor = jnp.where(jnp.isfinite(factor), factor, 1.0)
            # factor cast to each leaf's dtype: an fp32 scalar multiply
            # would re-widen bf16 grads outside the fused update chain
            grads = jax.tree_util.tree_map(
                lambda g: g * factor.astype(g.dtype), grads)

        sr_padded = self.bf16_sr_mode and bool(self._zero_pad_plan)
        if self.mixed_precision:
            opt_target = state.master
        elif sr_padded:
            # moments/grads live padded; join them for the update and
            # slice the padding back off for the stored params
            opt_target = self.zero_policy.encode(state.params,
                                                 self._zero_pad_plan)
        else:
            opt_target = state.params

        def do_update(target, opt_state):
            opt_state = self._with_lr(opt_state, lr)
            updates, new_opt = transform.update(
                grads, opt_state, target)
            if self.bf16_sr_mode:
                # fp32 updates land on bf16 params via stochastic
                # rounding — a deterministic bf16 add would swallow
                # updates below ulp(p) (bf16_optimizer.py docstring)
                from deepspeed_tpu.runtime.bf16_optimizer import \
                    stochastic_round_apply
                key = jax.random.fold_in(jax.random.PRNGKey(17),
                                         state.global_steps)
                new_target = stochastic_round_apply(target, updates, key)
            else:
                new_target = optax.apply_updates(target, updates)
            return new_target, new_opt

        def skip_update(target, opt_state):
            return target, opt_state

        if self.fp16_mode:
            new_target, new_opt = jax.lax.cond(
                overflow, skip_update, do_update, opt_target,
                state.opt_state)
        else:
            # overflow is statically False without fp16 loss scaling —
            # a lax.cond here would keep BOTH branches' outputs alive
            # (the skip branch returns the old params), blocking buffer
            # donation of params/opt_state into the update at exactly
            # the step's peak-memory point
            new_target, new_opt = do_update(opt_target, state.opt_state)

        if self.mixed_precision:
            new_master = new_target if local_axis is not None else \
                jax.lax.with_sharding_constraint(
                    new_target, self._master_pspecs_cached)
            new_params = jax.tree_util.tree_map(
                lambda m: m.astype(self.compute_dtype),
                self.zero_policy.decode(new_master, self._zero_pad_plan))
            if local_axis is None:
                new_params = jax.lax.with_sharding_constraint(
                    new_params, self._param_pspecs_cached)
        else:
            new_master = None
            if sr_padded:
                new_target = self.zero_policy.decode(new_target,
                                                     self._zero_pad_plan)
            new_params = new_target if local_axis is not None else \
                jax.lax.with_sharding_constraint(
                    new_target, self._param_pspecs_cached)

        dyn_args = self.dynamic_loss_scale_args() or {}
        new_scale = update_loss_scale(
            state.scale, overflow,
            scale_window=dyn_args.get(SCALE_WINDOW, 1000),
            min_scale=dyn_args.get(MIN_LOSS_SCALE, 1.0),
            delayed_shift=dyn_args.get(DELAYED_SHIFT, 2),
            dynamic=self.dynamic_loss_scale_enabled)

        if self._jit_gas() == 1 and not self._offload_enabled():
            new_acc = ()
        else:
            new_acc = _zeros_like_f32(state.acc_grads)
        new_state = EngineState(
            params=new_params, master=new_master, opt_state=new_opt,
            scale=new_scale,
            acc_grads=new_acc,
            skipped=state.skipped + overflow.astype(jnp.int32),
            global_steps=state.global_steps +
            (1 - overflow.astype(jnp.int32)))
        return new_state, overflow, grad_norm, health_grad

    def _resolve_step_lr(self, state, lr):
        """Inside-jit lr resolution: under async dispatch the host
        passes lr=None and the schedule is evaluated HERE, on the
        device-side count of successful steps — no host scalar ever
        rides the step. `global_steps` doesn't advance on an fp16
        overflow skip, so the schedule holds still across skipped
        steps exactly like the reference's host-side rewind. lr=None
        with no device schedule (client optax optimizer) passes
        through to `_with_lr`'s leave-untouched path."""
        if lr is None and self._device_lr_fn is not None:
            return self._device_lr_fn(state.global_steps)
        return lr

    def _with_lr(self, opt_state, lr):
        """Override injected learning_rate hyperparam with a traced scalar.
        lr=None (client optimizer with no scheduler) leaves the client's
        own learning rate untouched."""
        if lr is None:
            return opt_state
        if hasattr(opt_state, "hyperparams") and \
                "learning_rate" in opt_state.hyperparams:
            hp = dict(opt_state.hyperparams)
            hp["learning_rate"] = jnp.asarray(lr, jnp.float32)
            return opt_state._replace(hyperparams=hp)
        return opt_state

    def _scan_microbatches(self, micro_fn, acc0, stacked_batch, rng, gas,
                           force_scan=False):
        """Accumulate over the gas microbatches of a stacked [gas, ...]
        batch. micro_fn(mb, rng) -> (loss, grads, act_stats,
        router_stats). Returns (grads_or_acc, mean_loss, act_stats,
        router_stats) — act_stats ([L,3] device numerics health, or
        None) reduced over microbatches (max/mean/sum per column),
        router_stats ([E+2], or None) averaged over microbatches.
        gas==1 skips the accumulator and the per-microbatch rng fold
        (grads flow straight to the update) unless force_scan — the
        offload path always accumulates into its persistent buffer."""
        if gas == 1 and not force_scan:
            mb = jax.tree_util.tree_map(lambda x: x[0], stacked_batch)
            loss, grads, acts, rstats = micro_fn(mb, rng)
            return grads, loss, acts, rstats

        def body(carry, mb):
            acc, i = carry
            loss, grads, acts, rstats = micro_fn(
                mb, jax.random.fold_in(rng, i))
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            # acts/rstats=None are empty pytrees: scan stacks nothing
            return (acc, i + 1), (loss, acts, rstats)

        (acc, _), (losses, acts, rstats) = jax.lax.scan(
            body, (acc0, jnp.asarray(0, jnp.int32)), stacked_batch,
            length=gas)
        if acts is not None:
            from deepspeed_tpu.monitor import numerics as _num
            acts = _num.combine_act_microbatches(acts)
        if rstats is not None:
            # [gas, E+2] -> [E+2]: every entry (load/drop fractions,
            # aux) is a per-step mean quantity — average over the
            # accumulation window
            rstats = jnp.mean(rstats, axis=0)
        return acc, jnp.mean(losses), acts, rstats

    def _build_step_fns(self):
        mesh = self.mesh
        self._master_pspecs_cached = jax.tree_util.tree_map(
            lambda s: s, self._master_shardings)
        self._param_pspecs_cached = self._param_shardings

        # Explicit shard_map grads: needed when per-leaf DP collectives
        # diverge from dense psum (CSR sparse embedding grads).  Gated
        # to stage 0 with a pure data mesh — the same scope as the
        # reference's buffered_allreduce_fallback CSR path.
        self._use_shardmap_grads = (
            self._pure_data_mesh() and bool(self._sparse_grad_paths()))
        if self.sparse_gradients_enabled() and \
                not self._use_shardmap_grads and \
                self.mesh.shape[DATA_AXIS] > 1:
            logger.warning(
                "sparse_gradients requested but unavailable here "
                "(needs zero stage 0, a pure-data mesh, and a model "
                "exposing sparse_grad_paths()); using dense reduction")

        def micro_grad_fn(params, batch, rng, loss_scale, keep_prob):
            return self._micro_grad(params, batch, rng, loss_scale, keep_prob)

        self._micro_grad_jit = jax.jit(micro_grad_fn)

        def accum_fn(acc, grads):
            return jax.tree_util.tree_map(jnp.add, acc, grads)

        self._accum_jit = jax.jit(accum_fn, donate_argnums=(0,))

        def apply_fn(state, lr):
            lr = self._resolve_step_lr(state, lr)
            return self._unscale_clip_and_update(state, lr)

        self._apply_jit = jax.jit(apply_fn, donate_argnums=(0,))

        gas = self._jit_gas()

        if self._offload_enabled():
            self._build_offload_fns()

            def fused_grads_only(state, stacked_batch, rng, keep_prob):
                micro = lambda mb, r: self._micro_grad(
                    state.params, mb, r, state.scale.loss_scale, keep_prob)
                acc, loss, acts, rstats = self._scan_microbatches(
                    micro, state.acc_grads, stacked_batch, rng, gas,
                    force_scan=True)
                return state._replace(acc_grads=acc), loss, acts, rstats

            self._offload_grads_jit = jax.jit(fused_grads_only,
                                              donate_argnums=(0,))

        def fused_train_step(state, stacked_batch, rng, lr, keep_prob):
            """scan over gas microbatches then update; one compile."""
            lr = self._resolve_step_lr(state, lr)
            micro = lambda mb, r: self._micro_grad(
                state.params, mb, r, state.scale.loss_scale, keep_prob)
            out, loss, acts, rstats = self._scan_microbatches(
                micro, state.acc_grads, stacked_batch, rng, gas)
            if gas == 1:
                # no accumulator: grads flow straight into the update
                new_state, overflow, grad_norm, hgrad = \
                    self._unscale_clip_and_update(state, lr, grads=out)
            else:
                state = state._replace(acc_grads=out)
                new_state, overflow, grad_norm, hgrad = \
                    self._unscale_clip_and_update(state, lr)
            health = {"grad": hgrad, "act": acts} \
                if self._numerics_on else None
            return new_state, loss, overflow, grad_norm, health, rstats

        self._fused_step_jit = jax.jit(fused_train_step,
                                       donate_argnums=(0,))

        self._onebit_compressed_active = False
        self._onebit_warned_manual = False
        if self._use_onebit_shardmap:
            self._build_onebit_compressed_step()

        def eval_fn(params, batch):
            return self._loss_fn(params, batch, rngs=None,
                                 deterministic=True)

        self._eval_jit = jax.jit(eval_fn)

    def _build_onebit_compressed_step(self):
        """Compressed-phase 1-bit Adam step (ref `onebit_adam.py:330-372`):
        the whole train step runs inside one shard_map over the data
        axis. Gradients stay LOCAL to each data shard — there is no
        dense reduction anywhere in this program (the reference
        achieves this by flipping `enable_backward_allreduce = False`
        at freeze_step) — and the only cross-shard traffic is the
        bit-packed sign payload + one fp32 scale per worker inside
        `compressed_allreduce` (~1/32 of the dense fp32 wire volume).
        Params/opt-state are replicated in and provably identical out:
        every shard decodes the same gathered signs, so the update is
        deterministic across workers."""
        from jax import shard_map
        from deepspeed_tpu.runtime.fp16.onebit_adam import onebit_adam

        transform = onebit_adam(**self._onebit_kwargs,
                                axis_name=DATA_AXIS,
                                static_phase="compressed")
        mesh = self.mesh
        gas = self._jit_gas()

        def local_step(state, stacked_batch, rng, lr, keep_prob):
            lr = self._resolve_step_lr(state, lr)

            def micro(mb, mb_rng):
                mb_rng = jax.random.fold_in(
                    mb_rng, jax.lax.axis_index(DATA_AXIS))
                grad_fn = jax.value_and_grad(self._scaled_loss_fn,
                                             has_aux=True)
                (_, (raw_loss, _acts, _rstats)), grads = grad_fn(
                    state.params, mb, mb_rng, state.scale.loss_scale,
                    keep_prob)
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads)
                # numerics health + router stats are dropped on the
                # compressed 1-bit path (its shard_map out_specs
                # predate them)
                return (jax.lax.pmean(raw_loss, DATA_AXIS), grads,
                        None, None)

            grads, loss, _acts, _rstats = self._scan_microbatches(
                micro, _zeros_like_f32(state.params), stacked_batch,
                rng, gas)
            # with_health=False: nothing consumes health here — don't
            # even trace the stat reductions on the compressed path
            new_state, overflow, grad_norm, _hgrad = \
                self._unscale_clip_and_update(
                    state, lr, grads=grads, transform=transform,
                    local_axis=DATA_AXIS, with_health=False)
            return new_state, loss, overflow, grad_norm

        P = PartitionSpec

        def state_specs(state):
            """Everything replicated EXCEPT worker_error, whose leading
            [dp] dim is sharded over data: each worker owns its error-
            feedback slice (it diverges per worker by construction, so
            declaring it replicated would silently collapse it on
            checkpoint/reshard)."""
            specs = jax.tree_util.tree_map(lambda _: P(), state)
            return specs._replace(opt_state=specs.opt_state._replace(
                worker_error=jax.tree_util.tree_map(
                    lambda w: P(DATA_AXIS, *([None] * (w.ndim - 1))),
                    state.opt_state.worker_error)))

        def compressed_step(state, stacked_batch, rng, lr, keep_prob):
            batch_specs = stacked_batch_pspecs(stacked_batch)
            st_specs = state_specs(state)
            new_state, loss, overflow, grad_norm = shard_map(
                local_step, mesh=mesh,
                in_specs=(st_specs, batch_specs, P(), P(), P()),
                out_specs=(st_specs, P(), P(), P()),
                check_vma=False)(state, stacked_batch, rng, lr,
                                 keep_prob)
            # arity parity with _fused_step_jit (no numerics health or
            # router stats on the compressed path)
            return new_state, loss, overflow, grad_norm, None, None

        self._onebit_compressed_jit = jax.jit(compressed_step,
                                              donate_argnums=(0,))

    # ------------------------------------------------------------------
    # data path
    # ------------------------------------------------------------------
    def deepspeed_io(self, dataset, batch_size=None, route=C.ROUTE_TRAIN,
                     pin_memory=None, data_sampler=None, collate_fn=None,
                     num_local_io_workers=None):
        if route not in C.ROUTES:
            raise ValueError(
                f"deepspeed_io route must be one of {list(C.ROUTES)}, "
                f"got {route!r}")
        if batch_size is None:
            # Each process loads its share of the *global* microbatch
            # (micro_bs is per-device; one controller may host many devices).
            devices_per_process = max(
                1, self.dp_world_size // jax.process_count())
            batch_size = self.train_micro_batch_size_per_gpu() * \
                devices_per_process
        return DeepSpeedDataLoader(
            dataset=dataset,
            batch_size=batch_size,
            collate_fn=collate_fn or self.collate_fn,
            local_rank=jax.process_index(),
            tput_timer=self.tput_timer if route == C.ROUTE_TRAIN else None,
            data_parallel_world_size=jax.process_count(),
            data_parallel_rank=jax.process_index())

    def _shard_batch(self, batch):
        """Device-put a host batch with batch-dim sharding over the mesh."""
        def put(x):
            x = np.asarray(x)
            return jax.device_put(x, data_sharding(self.mesh, x.ndim))
        return jax.tree_util.tree_map(put, batch)

    # ------------------------------------------------------------------
    # train API
    # ------------------------------------------------------------------
    def _jit_gas(self):
        """Microbatch count the fused jitted step scans over. Pipeline
        engines fold microbatching inside the loss and override this."""
        return self.gradient_accumulation_steps()

    def _microbatches_per_step(self):
        """Microbatches consumed per train_batch call (micro_steps and
        throughput accounting); pipeline engines override."""
        return self._jit_gas()

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _keep_prob(self):
        if self.progressive_layer_drop is not None:
            return jnp.asarray(self.progressive_layer_drop.get_theta(),
                               jnp.float32)
        return self._keep_prob_one

    def _spans_active(self):
        """Record fwd/bwd/step spans when wall_clock_breakdown is on OR
        a Perfetto trace is being exported (monitor.trace.enabled) —
        the exporter renders the same fence-free spans as slices."""
        return self.wall_clock_breakdown() or \
            self.monitor.trace_export is not None

    def forward(self, batch, **kwargs):
        """Compute loss (and cache grads for `backward`)."""
        if self._spans_active():
            # fence-free span (monitor/trace.py): host dispatch time +
            # profiler TraceAnnotation, reported at sync fences — the
            # legacy path barriered the device TWICE per microstep here
            self.monitor.trace.start(SPAN_FORWARD)
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self._host_steps)
        batch = self._shard_batch(batch)
        self._tokens_pending += _batch_token_count(batch)
        # legacy-loop twin of train_batch's accounting: here batch is
        # ONE microbatch [rows, ...], so tokens/sample = trailing dims
        # (the deepspeed_io dataloader drives the tput timer on this
        # path, and the monitor's MFU derivation needs the ratio)
        lead = np.shape(jax.tree_util.tree_leaves(batch)[0]) \
            if jax.tree_util.tree_leaves(batch) else ()
        self._tokens_per_sample = int(np.prod(lead[1:])) \
            if len(lead) > 1 else 1
        loss, grads, acts, rstats = self._micro_grad_jit(
            self.state.params, batch, self._next_rng(),
            self.state.scale.loss_scale, self._keep_prob())
        self._pending_grads = grads
        self._pending_loss = loss
        # numerics health / router stats, manual path: the LAST
        # microbatch's stats stand in for the accumulation window
        # (device arrays, no sync; folded at the model step)
        self._pending_acts = acts
        self._pending_router = rstats
        if self._spans_active():
            self.monitor.trace.stop(SPAN_FORWARD)
        return loss

    __call__ = forward

    def backward(self, loss=None, allreduce_gradients=True, release_loss=False):
        """Fold the cached microbatch grads into the accumulator.

        release_loss=True drops the engine's own reference to the loss
        buffer (ref engine.py:934): `engine.losses` stays None and the
        device buffer frees as soon as the caller's reference dies —
        use it when the loop never reads `engine.losses`."""
        assert self._pending_grads is not None, \
            "backward() called without a preceding forward()"
        if self._spans_active():
            self.monitor.trace.start(SPAN_BACKWARD)
        if not jax.tree_util.tree_leaves(self.state.acc_grads):
            # gas=1 fast path keeps no persistent accumulator; the first
            # (only) microbatch's grads stand in directly
            acc = self._pending_grads
        else:
            acc = self._accum_jit(self.state.acc_grads,
                                  self._pending_grads)
        self.state = self.state._replace(acc_grads=acc)
        self._pending_grads = None
        if release_loss:
            self._pending_loss = None
            self.losses = None
        else:
            self.losses = loss if loss is not None else self._pending_loss
        if self._spans_active():
            self.monitor.trace.stop(SPAN_BACKWARD)
        return loss

    def _release_pending_loss(self):
        """Drop the forward()-cached loss reference at the end of
        step(): keeping it pinned would hold one stale device buffer
        alive across every subsequent step."""
        self._pending_loss = None

    def step(self, lr_kwargs=None):
        """Advance one micro step; at the grad-accum boundary, apply the
        model step (ref engine.py:955-1078)."""
        if self._spans_active():
            self.monitor.trace.start(SPAN_STEP)
        if self.is_gradient_accumulation_boundary():
            self._take_model_step(lr_kwargs)
        self.micro_steps += 1
        self._release_pending_loss()
        if self._spans_active():
            self.monitor.trace.stop(SPAN_STEP)

    def _take_model_step(self, lr_kwargs=None):
        lr = self._host_step_lr()
        tokens = self._tokens_pending
        self._tokens_pending = 0
        if self._offload_enabled():
            overflow = self._offload_take_step(lr)
            self._host_steps += 1
            if self.monitor.enabled:
                health = None
                if self._numerics_on:
                    health = {"grad": None,
                              "act": getattr(self, "_pending_acts",
                                             None)}
                    self._pending_acts = None
                router = self._pending_router
                self._pending_router = None
                self.monitor.on_step(
                    loss=self.losses, grad_norm=self._offload_last_norm,
                    loss_scale=self._host_scaler.cur_scale,
                    overflow=overflow, tokens=tokens,
                    wire_stats=self.wire_stats, health=health,
                    router=router)
            self._after_model_step(jnp.asarray(overflow))
            return
        if self._use_onebit_shardmap and not self._onebit_warned_manual \
                and self._host_steps >= self._onebit_freeze_step:
            # the compressed program exists only on the fused
            # train_batch path; the manual API would run warmup Adam
            # forever past freeze_step — say so once
            logger.warning(
                "OnebitAdam: forward()/backward()/step() never enters "
                "the compressed phase; use train_batch() to get the "
                "bit-packed collective past freeze_step")
            self._onebit_warned_manual = True
        self.state, overflow, grad_norm, hgrad = \
            self._apply_jit(self.state, lr)
        self._host_steps += 1
        if self.monitor.enabled:
            health = None
            if self._numerics_on:
                health = {"grad": hgrad,
                          "act": getattr(self, "_pending_acts", None)}
                self._pending_acts = None
            router = self._pending_router
            self._pending_router = None
            self.monitor.on_step(
                loss=self.losses, grad_norm=grad_norm,
                loss_scale=self.state.scale.loss_scale,
                overflow=overflow, tokens=tokens, health=health,
                router=router)
        self._after_model_step(overflow)

    def _next_lr(self):
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
            return float(self.lr_scheduler.get_last_lr()[0])
        if self._base_lr is None:
            # Client optax optimizer: its own schedule/lr applies unchanged.
            return None
        return float(self._base_lr)

    def _host_step_lr(self):
        """Per-step host half of the lr plumbing. Sync mode: advance
        the scheduler and return the concrete scalar (uploaded as a
        step argument). Async mode: advance the host scheduler as an
        OPTIMISTIC mirror — pure Python, no device work, exact except
        across fp16 overflow skips (fence-corrected) — and return None:
        the jitted step computes the lr on device."""
        if not self._async_dispatch:
            return self._next_lr()
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        return None

    def _sync_scheduler_mirror(self):
        """Correct the optimistic host scheduler mirror from the device
        step counter (one device_get). Only fp16 overflow skips can make
        the mirror drift, so this is a no-op everywhere else."""
        if self._async_dispatch and self.fp16_mode and \
                self.lr_scheduler is not None:
            gs = int(jax.device_get(self.state.global_steps))
            if self.lr_scheduler.last_batch_iteration != gs - 1:
                self.lr_scheduler.step(gs - 1)

    def _after_model_step(self, overflow):
        if self.fp16_mode and not self._async_dispatch:
            # Legacy synced loop: host-side scheduler rewind (parity:
            # scheduler doesn't advance past an overflow step in the
            # reference). This device_get serializes host and device
            # every step; async mode gets the same semantics for free
            # from the device-resident schedule.
            # ds-lint: allow[HOTSYNC] legacy synced loop only: the deliberate per-step rendezvous async mode exists to delete
            if bool(jax.device_get(overflow)) and \
                    self.lr_scheduler is not None:
                self.lr_scheduler.step(
                    self.lr_scheduler.last_batch_iteration - 1)
        # print fences are fences too: a steps_per_sync that doesn't
        # divide into the print multiples must not suppress
        # steps_per_print output
        if self._host_steps % self._steps_per_sync == 0 or \
                self._host_steps % self.steps_per_print() == 0:
            self._sync_fence()

    def _sync_fence(self):
        """The hot loop's only host<->device rendezvous: refresh the
        scheduler mirror and materialize device metrics (step counters,
        loss, lr, loss scale) for logging/TensorBoard. Runs every
        `steps_per_sync` optimizer steps (default: steps_per_print)."""
        self._sync_scheduler_mirror()
        at_print = self._host_steps % self.steps_per_print() == 0
        spans = None
        if self.monitor.enabled:
            # drains the device metric accumulator (ONE device_get per
            # fence), samples host gauges, emits to sinks, feeds the
            # stall watchdog
            event = self.monitor.on_fence()
            spans = event.get("spans") if event else None
        elif self.wall_clock_breakdown() and at_print:
            # wall_clock_breakdown without the monitor block: the trace
            # still accumulated span times; drain over the full print
            # window so the flag keeps producing output on its own
            spans = self.monitor.trace.drain()
        if at_print and spans:
            log_dist(
                "span ms/step (host dispatch, fence-aligned) | " +
                " | ".join(f"{k}: {v['ms_per']:.2f}"
                           for k, v in spans.items()),
                ranks=[0])
        if self.summary_writer is not None and at_print:
            gs = self.global_steps
            samples = gs * self.train_batch_size()
            self.summary_writer.add_scalar(
                "Train/Samples/lr", self._current_lr(), samples)
            if self.losses is not None:
                self.summary_writer.add_scalar(
                    "Train/Samples/train_loss",
                    float(np.asarray(jax.device_get(self.losses))),
                    samples)
            if self.fp16_mode:
                self.summary_writer.add_scalar(
                    "Train/Samples/loss_scale", self.loss_scale(),
                    samples)
            # the native writer buffers via the file object; make the
            # scalars visible to a live TensorBoard at print cadence
            self.summary_writer.flush()
        if at_print:
            # _current_lr, not get_lr(): the mirror was synced above and
            # get_lr() would pay a second device round trip for it
            log_dist(
                f"step={self.global_steps}, skipped={self.skipped_steps}, "
                f"lr={[self._current_lr()]}, mom={self.get_mom()}",
                ranks=[0])

    def stage_batch(self, batch):
        """Place a stacked [gas, micro_bs, ...] batch pytree on device
        with the engine's batch sharding (dim 1 over the data axis).
        Idempotent: leaves already staged as jax.Arrays skip the host
        np.asarray round trip (which would drag them BACK through the
        host link), and device_put reshards device-side — a no-op when
        the sharding already matches. Input pipelines call this ahead
        of time to prefetch; train_batch applies it to whatever it is
        handed."""
        # expert-parallel devices are data-parallel devices: batch rows
        # divide over (data, expert) when the mesh carries an expert
        # axis (deepspeed_tpu/moe/), over data alone otherwise
        row_axes = (DATA_AXIS, EXPERT_AXIS) \
            if expert_axis_size(self.mesh) > 1 else DATA_AXIS

        def put_stacked(x):
            if not isinstance(x, jax.Array):
                x = np.asarray(x)
            spec = [None] * np.ndim(x)
            if np.ndim(x) > 1:
                spec[1] = row_axes
            return jax.device_put(
                x, NamedSharding(self.mesh, PartitionSpec(*spec)))

        return jax.tree_util.tree_map(put_stacked, batch)

    def prefetch(self, data_source, depth=None, stacked=False):
        """Wrap a microbatch iterable in a background PrefetchLoader:
        collation + `stage_batch` placement run on a worker thread,
        `depth` (default async_dispatch.prefetch_depth) staged batches
        ahead of the step loop. Feed the result to `train_batch` as
        `data_iter`."""
        mon = self.monitor
        loader = PrefetchLoader(
            data_source, stage_fn=self.stage_batch, gas=self._jit_gas(),
            depth=depth if depth is not None else self.prefetch_depth(),
            stacked=stacked,
            heartbeat=(lambda: mon.heartbeat("prefetch"))
            if mon.enabled else None,
            finished=(lambda: mon.heartbeat_done("prefetch"))
            if mon.enabled else None,
            span=(lambda t0, dur: mon.subsystem_span(
                "prefetch", "stage_batch", t0, dur))
            if mon.trace_export is not None else None)
        # queue-occupancy gauge + stall-diagnosis heartbeats ride the
        # live loader
        self.monitor.attach_prefetch(loader)
        return loader

    def train_batch(self, data_iter=None, batch=None):
        """Fast path: one fused jitted step over all grad-accum
        microbatches. Pass an iterator yielding microbatches, a
        PrefetchLoader (pre-staged batches, no host collate here), or a
        pre-stacked batch pytree with leading dim [gas, micro_bs, ...].

        An exception escaping the step loop is a forensic moment: the
        flight recorder (monitor/flight.py) dumps the last events +
        heartbeat ages before it propagates (StopIteration — a merely
        exhausted data iterator — is not a crash)."""
        try:
            return self._train_batch_impl(data_iter=data_iter,
                                          batch=batch)
        except StopIteration:
            raise
        except BaseException as e:
            if self.monitor.enabled and \
                    not getattr(e, "_ds_flight_dumped", False):
                try:
                    e._ds_flight_dumped = True
                except Exception:  # ds-lint: allow[BROADEXC] exotic exception classes may reject attribute marks; dedup is best-effort
                    pass
                self.monitor.on_crash(e)
            raise

    def _step_outputs(self):
        """What the last dispatched step produced (the timers' fence)."""
        return self.state, self.losses

    def lower_train_step(self, batch):
        """The fused train step, lowered for a stacked [gas, rows, ...]
        `batch` with the arguments `train_batch` dispatches — for
        reading the program (its kernels, its memory) without running
        it or advancing the rng and lr state."""
        if self._offload_enabled():
            raise ValueError(
                "lower_train_step covers the fused device step; under "
                "ZeRO-Offload the update runs on the host")
        lr = None if self._async_dispatch else self._current_lr()
        return self._fused_step_jit.lower(
            self.state, self.stage_batch(batch), jax.random.PRNGKey(0),
            lr, self._keep_prob())

    def _train_batch_impl(self, data_iter=None, batch=None):
        gas = self._jit_gas()
        if batch is None:
            assert data_iter is not None
            if isinstance(data_iter, PrefetchLoader):
                # collated + staged on the prefetch worker thread
                batch = next(data_iter)
            else:
                micro = [next(data_iter) for _ in range(gas)]
                batch = jax.tree_util.tree_map(
                    lambda *xs: np.stack([np.asarray(x) for x in xs]),
                    *micro)
        else:
            leading = jax.tree_util.tree_leaves(batch)[0].shape[0]
            assert leading == gas, \
                f"stacked batch leading dim {leading} != gas {gas}"

        self.tput_timer.start()
        batch = self.stage_batch(batch)
        tokens = _batch_token_count(batch)
        # tokens per SAMPLE (static shape math, no device access): the
        # stacked batch is [gas, global_rows, ...] and tput counts
        # samples as rows — the monitor's tokens/s/chip + MFU derive
        # from this times avg_samples_per_sec
        lead = np.shape(jax.tree_util.tree_leaves(batch)[0]) \
            if jax.tree_util.tree_leaves(batch) else ()
        self._tokens_per_sample = int(np.prod(lead[2:])) \
            if len(lead) > 2 else 1
        lr = self._host_step_lr()
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self._host_steps)
        if self.flops_profiler_enabled() and \
                self._host_steps + 1 == self.flops_profiler_profile_step():
            self._profile_fused_step(batch, lr)
        if self._spans_active():
            self.monitor.trace.start(SPAN_STEP)
        health = None
        rstats = None
        if self._offload_enabled():
            self.state, loss, acts, rstats = self._offload_grads_jit(
                self.state, batch, self._next_rng(), self._keep_prob())
            overflow = jnp.asarray(self._offload_take_step(lr))
            grad_norm = None
            if self._numerics_on:
                health = {"grad": None, "act": acts}
        else:
            step_fn = self._fused_step_jit
            if self._use_onebit_shardmap:
                # Host-side phase switch at freeze_step (the XLA-native
                # form of ref onebit_adam.py:372's
                # enable_backward_allreduce flip): one recompile, after
                # which no dense grad reduction exists in the program.
                # Keyed on the OPTIMIZER's step count (like the
                # reference's state['step']) so a reload with
                # load_optimizer_states=False correctly re-warms; the
                # cheap host-step pre-check keeps the warmup hot loop
                # free of device_get syncs (count <= host steps always).
                if not self._onebit_compressed_active and \
                        self._host_steps >= self._onebit_freeze_step and \
                        int(jax.device_get(self.state.opt_state.count)) >= self._onebit_freeze_step:  # ds-lint: allow[HOTSYNC] host-step pre-check gates this fetch to at most one per run (the freeze_step phase switch)
                    self._onebit_compressed_active = True
                    log_dist(
                        "OnebitAdam: entering compressed phase "
                        f"(freeze_step={self._onebit_freeze_step}); "
                        "momentum now rides the bit-packed collective",
                        ranks=[0])
                if self._onebit_compressed_active:
                    step_fn = self._onebit_compressed_jit
            self.state, loss, overflow, grad_norm, health, rstats = \
                step_fn(self.state, batch, self._next_rng(), lr,
                        self._keep_prob())
        if self._spans_active():
            self.monitor.trace.stop(SPAN_STEP)
        mbs = self._microbatches_per_step()
        self.micro_steps += mbs
        self._host_steps += 1
        # losses before the fence: _sync_fence logs THIS step's loss
        self.losses = loss
        if self.monitor.enabled:
            if self._offload_enabled():
                self.monitor.on_step(
                    loss=loss, grad_norm=self._offload_last_norm,
                    loss_scale=self._host_scaler.cur_scale,
                    overflow=overflow, tokens=tokens,
                    wire_stats=self.wire_stats, health=health,
                    router=rstats)
            else:
                self.monitor.on_step(
                    loss=loss, grad_norm=grad_norm,
                    loss_scale=self.state.scale.loss_scale,
                    overflow=overflow, tokens=tokens, health=health,
                    router=rstats)
        self._after_model_step(overflow)
        # one fused step consumed `mbs` microbatches worth of samples
        self.tput_timer.stop(count=mbs)
        return loss

    def _profile_fused_step(self, batch, lr):
        """One-shot HLO cost-analysis profile of the fused train step
        (ref engine.py:803-832 drives FlopsProfiler at profile_step)."""
        from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler
        from deepspeed_tpu.profiling.flops_profiler.profiler import num_params
        prof = FlopsProfiler(self.module)
        prof.total_params = self._count_model_params(self.state.params)
        prof.start_profile()
        # fixed key: profiling must not perturb the training RNG stream
        prof_rng = jax.random.PRNGKey(0)
        try:
            if self._offload_enabled():
                prof.profile_jitted(self._offload_grads_jit, self.state,
                                    batch, prof_rng,
                                    self._keep_prob(), measure_time=False)
            else:
                prof.profile_jitted(self._fused_step_jit, self.state, batch,
                                    prof_rng, lr, self._keep_prob(),
                                    measure_time=False)
        except Exception as e:  # donated-buffer retrace edge cases
            import traceback
            logger.warning(
                f"flops profile failed: {e}\n{traceback.format_exc()}")
            return
        prof.stop_profile()
        prof.print_model_profile(
            profile_step=self.flops_profiler_profile_step(),
            module_depth=self.flops_profiler_module_depth(),
            top_modules=self.flops_profiler_top_modules(),
            detailed=self.flops_profiler_detailed())

    def eval_batch(self, batch):
        batch = self._shard_batch(batch)
        return self._eval_jit(self.state.params, batch)

    def allreduce_gradients(self, bucket_size=MEMORY_OPT_ALLREDUCE_SIZE):
        """No-op under SPMD: gradient reduction is compiled into the step
        (kept for API parity with ref engine.py:836)."""
        return None

    def train(self, mode=True):
        self._training = mode
        return self

    def eval(self):
        return self.train(False)

    # ------------------------------------------------------------------
    # properties
    # ------------------------------------------------------------------
    @property
    def global_steps(self):
        """Total optimizer steps taken (successful + overflow-skipped).
        Every step bumps exactly one of the two device counters, so the
        sum equals the host step mirror EXACTLY (not just optimistically)
        — under async dispatch it is served from the mirror with no
        device sync. Otherwise both counters come back in one fused
        fetch instead of two sequential device_get round trips."""
        if self._async_dispatch:
            return self._host_steps
        gs, sk = jax.device_get((self.state.global_steps,
                                 self.state.skipped))
        return int(gs) + int(sk)

    @property
    def skipped_steps(self):
        return int(jax.device_get(self.state.skipped))

    @property
    def params(self):
        return self.state.params

    def module_state_dict(self):
        """Full fp32 module weights on host (ref `engine.py:1248`);
        multi-host shardings are gathered via process_allgather."""
        return _fetch_to_host(self.fp32_params)

    def _module_ckpt_template(self):
        """Template handed to per-layer checkpoint loaders; engines with
        a non-tree stored layout override this with the logical tree."""
        return self.state.params

    def _module_from_ckpt(self, tree):
        """Convert a loaded logical module tree into the engine's stored
        layout (identity for tree-layout engines)."""
        return tree

    def _logical_module_tree(self, stored):
        """Convert a stored-layout fp32/compute module tree into the
        module's logical tree for serialization (identity here; the
        pipeline engine unflattens its per-stage flat layout)."""
        return stored

    @property
    def fp32_params(self):
        if self._offload_enabled():
            # copy=True: on the CPU backend jnp.asarray may ALIAS the
            # numpy buffer, and _host_master is updated in place by
            # every subsequent optimizer step — a caller holding this
            # tree would silently see it mutate
            return self._offload_unravel(
                jnp.array(self._host_master, copy=True))
        if self.mixed_precision:
            return self.zero_policy.decode(self.state.master,
                                           self._zero_pad_plan)
        return self.state.params

    # ------------------------------------------------------------------
    # checkpointing (ref engine.py:1248-1573; layout preserved)
    # ------------------------------------------------------------------
    def _ckpt_payload(self, state):
        """The checkpoint-facing device trees decoded from live state
        (pad-plan leaves in true unpadded shapes so the checkpoint
        stays elastic across dp sizes)."""
        payload = dict(
            opt_state=self.zero_policy.decode(
                state.opt_state, self._zero_pad_plan,
                suffix_match=True),
            scale=state.scale,
            global_steps=state.global_steps,
            skipped=state.skipped)
        if not self._offload_enabled():
            if self.mixed_precision:
                payload["module"] = self.zero_policy.decode(
                    state.master, self._zero_pad_plan)
            else:
                payload["module"] = state.params
        return payload

    def _build_ckpt_snapshot_fn(self):
        """Jitted snapshot: decode the checkpoint-facing trees from the
        live state and copy every leaf into FRESH buffers. The copies
        cannot alias the state the step functions donate, so training
        can keep stepping while the writer serializes."""
        return jax.jit(lambda state: jax.tree_util.tree_map(
            jnp.copy, self._ckpt_payload(state)))

    def _checkpoint_snapshot(self, client_state, isolate=True):
        """Phase 1 of save_checkpoint — the only part the train loop
        pays for: one jitted device-side copy (dispatched async) plus
        host memcpys of the ZeRO-Offload master/moments/wire state
        (taken before the next host Adam step can mutate them).
        isolate=False (inline writes: sync and multi-process saves)
        skips every copy and serializes straight from live state — the
        legacy sync path's memory profile; nothing steps while an
        inline write runs, so aliasing is safe."""
        if isolate:
            if self._ckpt_snapshot_jit is None:
                self._ckpt_snapshot_jit = self._build_ckpt_snapshot_fn()
            payload = self._ckpt_snapshot_jit(self.state)
        else:
            payload = self._ckpt_payload(self.state)
        snap = dict(
            # PipelineModule-style models write one file per layer so
            # the checkpoint reloads onto any stage partitioning
            # (ref pipe/module.py:536-567)
            per_layer=hasattr(self.module, "save_state_dict") and
            hasattr(self.module, "load_state_dir"),
            payload=payload,
            # _rng buffers are replaced (never donated) by _next_rng,
            # so the reference stays valid without a copy
            rng=self._rng,
            meta=dict(
                micro_steps=self.micro_steps,
                dp_world_size=self.dp_world_size,
                lr_scheduler=self.lr_scheduler.state_dict()
                if self.lr_scheduler else None),
            # deep copy: the caller (and the training loop) may keep
            # mutating nested client_state values while the background
            # writer serializes — the snapshot must freeze them now
            client_state=copy.deepcopy(dict(client_state or {})),
            # the EFFECTIVE stage (may be capped under pipe flat mode);
            # checkpoint metadata must describe what actually ran
            zero_stage=self.zero_policy.stage,
        )
        if self._offload_enabled():
            snap.update(self._offload_checkpoint_snapshot(
                isolate=isolate))
            snap["module"] = self._logical_module_tree(snap["module"])
        else:
            # logical layout for the writer; the pipe engine's override
            # slices the snapshot buffers (still async, no host fetch)
            snap["module"] = self._logical_module_tree(payload["module"])
        return snap

    def _write_checkpoint(self, save_dir, tag, snap, save_latest,
                          commit_gate=None, writer=None):
        """Phase 2 (runs on the background writer thread under
        async_save): device_get the snapshot and serialize into a
        `<tag>.tmp` staging dir, fsync, atomically rename to `<tag>`,
        update `latest` LAST, then rotate per checkpoint.keep_last.
        `commit_gate` (from AsyncCheckpointWriter.submit) orders the
        commit sections of concurrent writers by submission. `writer`
        is the owning AsyncCheckpointWriter: a job whose writer was
        ABANDONED still commits its tag dir but skips the `latest`
        update and rotation (it may be racing a successor engine that
        already committed newer tags)."""
        import time as _time
        write_t0 = _time.perf_counter()
        self.monitor.heartbeat("checkpoint")
        multi_proc = jax.process_count() > 1

        def _barrier(phase):
            # shared-filesystem commit protocol: every process's shard
            # writes must land before process 0 renames, and no process
            # may return before the commit is visible
            if multi_proc:
                from jax.experimental import multihost_utils
                multihost_utils.sync_global_devices(f"ckpt_{phase}_{tag}")

        staging = ckpt_io.staging_dir(save_dir, tag)
        if os.path.exists(staging) and jax.process_index() == 0:
            import shutil
            shutil.rmtree(staging)   # stale leftover of a killed save
        _barrier("begin")
        os.makedirs(staging, exist_ok=True)
        payload = snap["payload"]
        gs, sk = jax.device_get((payload["global_steps"],
                                 payload["skipped"]))
        if snap["per_layer"]:
            # all processes participate (per-layer gathers are
            # collectives on multi-host shardings); proc 0 writes
            self.module.save_state_dict(staging, snap["module"])
        # module/opt_state stay as (possibly sharded) jax arrays: the
        # writer streams each process's addressable shards to its own
        # zero_pp_rank files — no host gather (ref engine.py:1522-1531).
        sd = dict(
            module={} if snap["per_layer"] else snap["module"],
            global_steps=int(gs) + int(sk),
            skipped_steps=int(sk),
            micro_steps=snap["meta"]["micro_steps"],
            dp_world_size=snap["meta"]["dp_world_size"],
            lr_scheduler=snap["meta"]["lr_scheduler"],
            rng=jax.device_get(snap["rng"]),
        )
        sd.update(snap["client_state"])
        optim_sd = dict(
            opt_state=payload["opt_state"],
            scale=jax.device_get(payload["scale"]),
            zero_stage=snap["zero_stage"],
        )
        if "host_adam" in snap:
            optim_sd["host_adam"] = snap["host_adam"]
            optim_sd["host_master"] = snap["host_master"]
            if "offload_wire" in snap:
                optim_sd["offload_wire"] = snap["offload_wire"]
        save_checkpoint_files(save_dir, tag, sd, optim_sd,
                              ckpt_dir=staging)
        _barrier("staged")
        with (commit_gate() if commit_gate is not None
              else contextlib.nullcontext()):
            if jax.process_index() == 0:
                ckpt_io.commit_staging_dir(save_dir, tag)
                stale = writer is not None and writer.abandoned.is_set()
                if stale:
                    logger.warning(
                        f"abandoned checkpoint writer committed tag "
                        f"'{tag}' but is leaving `latest` and rotation "
                        "alone (a successor engine may own them now)")
                if save_latest and not stale:
                    write_latest_tag(save_dir, tag)
                keep_last = self.checkpoint_keep_last()
                if keep_last and not stale:
                    deleted = ckpt_io.rotate_checkpoints(
                        save_dir, keep_last, protect=(tag,))
                    if deleted:
                        log_dist("checkpoint rotation removed "
                                 f"{deleted}", ranks=[0])
        _barrier("committed")
        if self.monitor.enabled:
            # runs on the writer thread under async_save — the monitor
            # event path and counters are thread-safe by contract
            commit_ms = (_time.perf_counter() - write_t0) * 1e3
            self.monitor.registry.inc("ckpt/commits")
            self.monitor.registry.set_counter("ckpt/last_commit_ms",
                                              round(commit_ms, 2))
            self.monitor.heartbeat("checkpoint")
            self.monitor.event(
                "ckpt_commit", tag=str(tag), dir=save_dir,
                wall_ms=round(commit_ms, 2),
                global_steps=int(gs) + int(sk))
        log_dist(f"saved checkpoint {tag} to {save_dir}", ranks=[0])

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True, async_save=None):
        """Snapshot-then-write checkpoint save. With
        checkpoint.async_save (default true) the call returns after the
        device-side snapshot; a background thread serializes into a
        staging dir and commits atomically (`wait_for_checkpoint` is
        the barrier). `async_save` overrides the config per call.
        Returns False only when checkpoint.queue_policy="drop"
        discarded the save under backpressure."""
        # the checkpoint must carry the TRUE schedule position, not the
        # optimistic async mirror (drifts across fp16 overflow skips)
        self._sync_scheduler_mirror()
        if tag is None:
            tag = f"global_step{self.global_steps}"
        # a still-running ABANDONED writer may own this tag's shared
        # `<tag>.tmp` staging dir (recovery replays regenerate the
        # same tag names); writing into it concurrently would commit a
        # torn mix of two saves — skip, the next boundary's tag is free
        for w in list(getattr(self, "_abandoned_ckpt_writers", [])):
            if not w.pending():
                self._abandoned_ckpt_writers.remove(w)
            elif w.tag_in_flight(tag):
                logger.warning(
                    f"skipping checkpoint save '{tag}': an abandoned "
                    "writer still holds this tag's staging dir")
                return False
        if self.checkpoint_tag_validation_enabled():
            validate_checkpoint_tag(
                tag, fail_on_mismatch=self.checkpoint_tag_validation_fail())
        if async_save is None:
            async_save = self.checkpoint_async_save()
        if async_save and jax.process_count() > 1:
            # the shared-dir commit protocol barriers across processes;
            # running those collectives on a writer thread while the
            # main thread dispatches step collectives is a deadlock
            # trap — multi-process saves stay inline
            log_dist(
                "checkpoint.async_save: forced off under multi-process "
                "(the commit barrier is a collective; it must not run "
                "on a background thread)", ranks=[0])
            async_save = False
        if async_save:
            if self._ckpt_writer is None:
                self._ckpt_writer = ckpt_io.AsyncCheckpointWriter(
                    queue_depth=self.checkpoint_writer_queue_depth(),
                    queue_policy=self.checkpoint_queue_policy())
            # queue_policy="drop" decides BEFORE the snapshot is built:
            # a dropped save must not pay the device copy + host
            # memcpys it is dropping
            if not self._ckpt_writer.admit(tag):
                return False
        with self.monitor.trace.span(SPAN_CKPT):
            # the only part of an async save the train loop pays for
            snap = self._checkpoint_snapshot(client_state,
                                             isolate=async_save)
        if not async_save:
            # an in-flight async writer may hold this tag's staging dir
            # or commit `latest` after us — drain it before an inline
            # write touches the same save_dir (the snapshot above has
            # already frozen the state this save will contain)
            self.wait_for_checkpoint()
            self._write_checkpoint(save_dir, str(tag), snap, save_latest)
            return True
        # memory ledger: the snapshot's fresh double-buffers are alive
        # from here until the writer finishes (success or failure) —
        # exactly the window an OOM post-mortem needs attributed
        tokens = self._register_ckpt_snapshot(str(tag), snap)
        led = self.monitor.ledger
        writer = self._ckpt_writer
        try:
            accepted = writer.submit(
                lambda commit_gate: self._write_checkpoint(
                    save_dir, str(tag), snap, save_latest,
                    commit_gate=commit_gate, writer=writer),
                tag,
                on_done=lambda: [led.release(t) for t in tokens])
        except BaseException:
            # submit re-raises pending writer errors BEFORE accepting
            # the job — a leaked entry would pollute every later
            # memory event with a phantom snapshot
            for t in tokens:
                led.release(t)
            raise
        if not accepted:
            for t in tokens:
                led.release(t)
        return accepted

    def _register_ckpt_snapshot(self, tag, snap):
        """Register the isolated snapshot's copies with the memory
        ledger: device payload buffers (per-device bytes) + the
        offload host memcpys. Entry names carry a per-engine sequence
        number — a re-save of the SAME tag while the first write is in
        flight must not replace the first save's entries (whose
        on_done would then release the live second snapshot). Returns
        the tokens the writer's on_done releases."""
        from deepspeed_tpu.monitor import memory as _mem
        led = self.monitor.ledger
        seq = self._ckpt_snap_seq = \
            getattr(self, "_ckpt_snap_seq", 0) + 1
        name = f"snapshot:{tag}@{seq}"
        tokens = [led.register_tree(_mem.CAT_CKPT, name,
                                    snap["payload"])]
        host = 0
        if "host_master" in snap:
            host += int(snap["host_master"].nbytes)
        for v in (snap.get("host_adam") or {}).values():
            if isinstance(v, np.ndarray):
                host += int(v.nbytes)
        for v in (snap.get("offload_wire") or {}).values():
            if isinstance(v, np.ndarray):
                host += int(v.nbytes)
        if host:
            tokens.append(led.register(
                _mem.CAT_CKPT, f"{name}#host", host,
                space=_mem.SPACE_HOST))
        return tokens

    def wait_for_checkpoint(self, timeout=None):
        """Barrier for in-flight async saves: returns once every
        submitted checkpoint is durably committed (staging dir renamed,
        `latest` updated) and re-raises the first background write
        error. load_checkpoint calls this implicitly; call it yourself
        before shutdown or before reading checkpoints externally.

        `timeout` (seconds) bounds the wait: on expiry a
        `CheckpointWaitTimeout` is raised carrying the writer's last
        heartbeat age, so a supervisor can abandon a hung writer
        (`abandon_checkpoint_writers`) and rebuild instead of blocking
        teardown on it. (Writer threads stay non-daemon by design —
        the interpreter never exits mid-write — so abandonment frees
        the ENGINE, not final process exit, from a wedged writer.)"""
        if self._ckpt_writer is None:
            return
        if self._ckpt_writer.wait(timeout):
            return
        hb, _ = self.monitor._heartbeat_state()
        age = hb.get("checkpoint")
        pending = self._ckpt_writer.pending()
        raise ckpt_io.CheckpointWaitTimeout(
            f"{pending} async checkpoint save(s) still in flight after "
            f"{timeout}s; writer heartbeat "
            + (f"{age}s ago" if age is not None else "never seen")
            + " — abandon_checkpoint_writers() detaches them (the "
            "committed `latest` tag is unaffected)",
            pending=pending, heartbeat_age_sec=age)

    def abandon_checkpoint_writers(self):
        """Detach in-flight async save jobs: the engine stops tracking
        (and waiting on) them. Running writer threads finish or fail
        on their own — their tag dirs still commit atomically — but an
        abandoned job no longer moves `latest` or rotates: a stale
        writer unwedging AFTER a successor engine committed newer tags
        must not regress the pointer to an older save. Their errors
        are no longer re-raised into the train loop. Returns the
        number of jobs abandoned. The next save_checkpoint builds a
        fresh writer."""
        writer, self._ckpt_writer = self._ckpt_writer, None
        if writer is None:
            return 0
        writer.abandoned.set()
        # remembered so later saves refuse to touch a tag whose
        # staging dir a still-running abandoned job may own
        self._abandoned_ckpt_writers = [
            w for w in getattr(self, "_abandoned_ckpt_writers", [])
            if w.pending()] + [writer]
        abandoned = writer.pending()
        if abandoned:
            logger.warning(
                f"abandoning {abandoned} in-flight async checkpoint "
                "save(s); their tag dirs (if completed) remain atomic "
                "but they will not move `latest`, and their errors "
                "will no longer propagate")
        return abandoned

    def shutdown(self, wait_for_checkpoint=True,
                 checkpoint_timeout=None):
        """Tear down the engine's host-side services so it can be
        dropped and rebuilt (the elastic supervisor's recovery path):
        drain — or, on timeout, abandon — in-flight checkpoint writers,
        then close the monitor (watchdog thread, flight recorder
        disarm, sink flush). Device state is freed by GC once the last
        reference to the engine goes away."""
        if wait_for_checkpoint:
            try:
                self.wait_for_checkpoint(timeout=checkpoint_timeout)
            except ckpt_io.CheckpointWaitTimeout as e:
                logger.warning(f"shutdown: {e}")
                self.abandon_checkpoint_writers()
            except RuntimeError as e:
                # a failed background write must not block teardown
                logger.warning(f"shutdown: pending writer error: {e}")
        self.monitor.close()

    def load_checkpoint(self, load_dir, tag=None,
                        load_module_strict=True,
                        load_optimizer_states=True,
                        load_lr_scheduler_states=True,
                        retries=0):
        # a save of the checkpoint being loaded may still be in flight
        self.wait_for_checkpoint()
        if tag is None:
            tag = read_latest_tag(load_dir, retries=retries)
            if tag is None:
                logger.warning(
                    f"Unable to find latest file at {load_dir}/latest")
                return None, {}
        aux_templates = {"scale": jax.device_get(self.state.scale)}
        if self._offload_enabled():
            aux_templates["host_master"] = self._host_master
            aux_templates["host_adam"] = self._host_adam.state_dict()
            if self._config.zero_config.offload_wire_compressed():
                aux_templates["offload_wire"] = \
                    self._offload_wire_state_dict()
        per_layer = hasattr(self.module, "save_state_dict") and \
            hasattr(self.module, "load_state_dir")
        sd, optim_sd = load_checkpoint_files(
            load_dir, tag, zero_enabled=load_optimizer_states,
            module_template=None if per_layer else self.state.params,
            opt_state_template=self.state.opt_state,
            aux_templates=aux_templates, retries=retries)
        if per_layer and "module" not in sd:
            # template/conversion hooks: engines whose stored layout
            # differs from the module's logical tree (PipelineEngine's
            # per-stage flat layout) translate here
            sd["module"] = self._module_from_ckpt(
                self.module.load_state_dir(
                    os.path.join(load_dir, str(tag)),
                    self._module_ckpt_template()))

        # Under ZeRO-Offload the fp32 master lives in pinned host memory
        # (state.master is None); rebuilding a device master here would
        # defeat offload and risk OOM (mirrors _init_state). SR mode
        # likewise must not materialize an fp32 tree on DEVICE — at
        # 1.5B a 6.2 GB fp32 detour next to the live bf16 state would
        # OOM the 16 GB chip this mode exists for; checkpoint leaves
        # are host numpy here, so cast leaf-wise on upload.
        if self.bf16_sr_mode:
            params = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(
                    jnp.asarray(x, self.compute_dtype), s),
                sd["module"], self._param_shardings)
            master = None
        elif self.mixed_precision or self._offload_enabled():
            params_f32 = jax.tree_util.tree_map(
                lambda x: jnp.asarray(x, jnp.float32), sd["module"])
            params = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(
                    jnp.asarray(x, self.compute_dtype), s),
                params_f32, self._param_shardings)
            master = jax.device_put(
                self.zero_policy.encode(params_f32, self._zero_pad_plan),
                self._master_shardings) if self.mixed_precision else None
        else:
            params_f32 = jax.tree_util.tree_map(
                lambda x: jnp.asarray(x, jnp.float32), sd["module"])
            master = None
            params = jax.device_put(params_f32, self._param_shardings)

        if self._offload_enabled():
            # keep host masters in sync with the restored weights even
            # when optimizer state isn't being loaded
            from jax.flatten_util import ravel_pytree
            flat, _ = ravel_pytree(params_f32)
            self._host_master[:] = np.asarray(jax.device_get(flat))
            if self._config.zero_config.offload_wire_compressed():
                # shadow/device copy resync to the restored masters; a
                # wire state dict loaded below may overwrite this
                self._offload_wire_load_state_dict(None)

        opt_state = self.state.opt_state
        scale = self.state.scale
        if load_optimizer_states and optim_sd is not None and \
                self._offload_enabled():
            if "host_master" in optim_sd:
                self._host_master[:] = optim_sd["host_master"]
                self._host_adam.load_state_dict(optim_sd["host_adam"])
                self._host_scaler.cur_scale = float(
                    np.asarray(optim_sd["scale"][0]))
                scale = make_static_loss_scale_state(
                    self._host_scaler.cur_scale)
                if self._config.zero_config.offload_wire_compressed():
                    # restores the error-feedback residual / param
                    # shadow, or resyncs them to the loaded masters when
                    # the checkpoint was written without wire state
                    self._offload_wire_load_state_dict(
                        optim_sd.get("offload_wire"))
            else:
                # checkpoint written without offload: masters restore
                # from the saved fp32 module weights; moments restart
                logger.warning(
                    "checkpoint has no host-offload optimizer state "
                    "(saved without cpu_offload?); masters restored "
                    "from module weights, Adam moments reset")
        elif load_optimizer_states and optim_sd is not None:
            if optim_sd.get("opt_state") is None:
                # loader's structure-mismatch fallback (checkpoint saved
                # with a different optimizer): keep fresh moments
                logger.warning(
                    "checkpoint optimizer state does not match the "
                    "current optimizer (different type?); optimizer "
                    "moments reset")
            else:
                # checkpoints store true shapes; re-enter the padded
                # layout (computed for the CURRENT dp size — elastic)
                restored = self.zero_policy.encode(
                    jax.tree_util.tree_map(jnp.asarray,
                                           optim_sd["opt_state"]),
                    self._zero_pad_plan, suffix_match=True)
                mismatched = []

                def put(cur, saved):
                    if saved.shape != cur.shape:
                        # per-worker state saved at a different world
                        # size (1-bit Adam worker_error [old_dp, ...]):
                        # keep the fresh init — error feedback is
                        # worker-local and safely restarts from zero
                        mismatched.append((saved.shape, cur.shape))
                        return cur
                    return jax.device_put(saved, cur.sharding)

                opt_state = jax.tree_util.tree_map(
                    put, self.state.opt_state, restored)
                if mismatched:
                    logger.warning(
                        f"{len(mismatched)} optimizer-state leaves were "
                        "saved at a different world size and were reset "
                        f"(e.g. {mismatched[0][0]} vs {mismatched[0][1]})")
            if optim_sd.get("scale") is not None and self.fp16_mode:
                # only fp16 mode unscales grads; restoring a saved
                # scale != 1 into a bf16/fp32 engine (e.g. migrating an
                # fp16 checkpoint) would scale every grad forever
                scale = LossScaleState(*[jnp.asarray(x)
                                         for x in optim_sd["scale"]])

        if self._jit_gas() == 1 and not self._offload_enabled():
            acc_restored = ()
        else:
            # _params_enc_template is abstract (ShapeDtypeStructs in SR
            # mode, where no concrete params_f32 tree exists) and already
            # in the padded/encoded layout — same recipe as _init_state.
            acc_restored = jax.device_put(
                _zeros_like_f32(self._params_enc_template),
                self._acc_shardings)
        self.state = self._scalars_on_mesh(EngineState(
            params=params, master=master, opt_state=opt_state, scale=scale,
            acc_grads=acc_restored,
            skipped=jnp.asarray(sd.get("skipped_steps", 0), jnp.int32),
            global_steps=jnp.asarray(
                sd.get("global_steps", 0) - sd.get("skipped_steps", 0),
                jnp.int32)))
        self.micro_steps = sd.get("micro_steps", 0)
        # the checkpoint's global_steps already counts successful +
        # skipped optimizer steps — deriving from micro_steps instead
        # would drift whenever the resuming run uses a different
        # gradient_accumulation_steps than the saving run
        self._host_steps = int(sd.get("global_steps", 0))
        # re-derive the 1-bit Adam phase: the next train_batch re-checks
        # the restored optimizer count (a load with
        # load_optimizer_states=False resets count=0 and correctly
        # re-warms rather than freezing an all-zero variance)
        self._onebit_compressed_active = False
        if "rng" in sd and sd["rng"] is not None:
            self._rng = jnp.asarray(sd["rng"])

        if load_lr_scheduler_states and self.lr_scheduler is not None and \
                sd.get("lr_scheduler") is not None:
            self.lr_scheduler.load_state_dict(sd["lr_scheduler"])

        client_state = {
            k: v for k, v in sd.items()
            if k not in ("module", "module_flat", "global_steps",
                         "skipped_steps", "micro_steps", "dp_world_size",
                         "lr_scheduler", "rng")
        }
        log_dist(f"loaded checkpoint {tag} from {load_dir}", ranks=[0])
        return f"{load_dir}/{tag}", client_state

