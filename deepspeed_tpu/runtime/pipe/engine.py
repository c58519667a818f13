"""PipelineEngine — pipeline-parallel training on the SPMD substrate.

Counterpart of `deepspeed/runtime/pipe/engine.py:45` (1169 LoC). The
reference interprets an instruction stream per stage process
(`_INSTRUCTION_MAP`, ref `engine.py:1135-1161`) with p2p sends/recvs and
ring buffers. Under single-controller SPMD both the schedule and the
communication are *compiled*:

  * arbitrary PipelineModules (heterogeneous layers/shapes) on a
    pipe>1 mesh execute the compiled 1F1B interpreter
    (`pipe/interp.py`): the TrainSchedule instruction streams are
    clock-aligned at build time and lowered to a shard_map scan whose
    pipe shards each run THEIR stage via lax.switch, with ppermute
    activation/cotangent flow, recompute-based backward bounded by
    `num_pipe_buffers()` saved stage inputs, and per-stage parameter
    memory partitioning (`pipe/flat_params.py`). This is the
    RECOMMENDED substrate: 1F1B's activation bound beats GPipe's m
    residual sets, and parameters divide by the stage count. Which
    of the two is faster end to end is not measured on the chip
    (no pipeline cell yet: ROADMAP, Design item 4). On a serialized
    virtual test mesh the scan's fill/drain bubble executes as real
    garbage compute, an overhead factor of 1 + (S-1)/m, which says
    nothing about parallel hardware:
    there both paths pay the bubble as idle stages (an analytic
    claim; the four-chip host is where it would be measured, and
    nothing has been measured there yet).
    On a pipe=1 mesh the layer chain runs sequentially
    inside the fused step (pure microbatching semantics, no overlap to
    be had).
  * homogeneous-stage models (the PipelinedGPT2 protocol: stacked
    [S, ...] stage params + shape-preserving stage body) execute the
    GPipe fill/steady/drain timeline inside ONE jitted step —
    `lax.scan` over ticks, vmapped stage body partitioned over the
    `pipe` mesh axis, activation rotation lowered to collective-permute
    (see `models/gpt2_pipe.py`). Backward-pipeline scheduling falls
    out of autodiff — the simplest template for fully-regular stacks
    and the one that composes with Megatron TP on the `model` axis.

The train_batch/eval_batch API and loss aggregation semantics
(ref `engine.py:244,320,388-418`) are preserved.
"""

import functools
import inspect

import jax
import numpy as np

from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.mesh import DATA_AXIS, MODEL_AXIS, PIPE_AXIS
from deepspeed_tpu.runtime.pipe.module import PipelineModule
from deepspeed_tpu.runtime.pipe.topology import PipelineParallelGrid
from deepspeed_tpu.runtime.pipe.schedule import TrainSchedule
from deepspeed_tpu.utils.logging import log_dist


def is_pipelined_model(model):
    """True for models implementing the stacked-stage SPMD pipeline
    protocol (PipelinedGPT2 and friends): stage_module + loss_fn."""
    return hasattr(model, "stage_module") and hasattr(model, "loss_fn")


class PipelineEngine(DeepSpeedEngine):
    """Training engine for pipelined models (ref `pipe/engine.py:45`)."""

    # Bound on distinct compiled eval-1F1B programs kept alive (one per
    # eval batch shape); LRU beyond this.
    _EVAL_INTERP_CACHE_MAX = 4

    def __init__(self, *args, **kwargs):
        model = kwargs.get("model")
        self._is_pipe_module = isinstance(model, PipelineModule)
        self._pipelined_protocol = is_pipelined_model(model)
        super().__init__(*args, **kwargs)

        # Under single-controller SPMD every process drives the whole
        # device mesh, so each process logically holds ALL stages —
        # global_rank 0 keeps the mpu predicates true everywhere (a
        # per-stage multi-controller runtime would pass its real rank).
        self.grid = PipelineParallelGrid(mesh=self.mesh, global_rank=0)
        self.num_stages = self.mesh.shape[PIPE_AXIS]
        self.stage_id = self.grid.get_stage_id()
        self.micro_batches = self.gradient_accumulation_steps()

        if self.elasticity_enabled():
            raise RuntimeError(
                "Elasticity is not currently supported with pipeline "
                "parallelism.")  # parity: ref pipe/engine.py:57
        if self._is_pipe_module and self.pld_enabled():
            from deepspeed_tpu.utils.logging import logger
            if getattr(self, "_pipe_flat_mode", False):
                logger.warning(
                    "progressive_layer_drop has no effect under the "
                    "compiled 1F1B executor: stochastic depth makes the "
                    "per-stage clock tables data-dependent (documented "
                    "exclusion, docs/tutorials/progressive-layer-drop.md)"
                )
            elif not getattr(self, "_pld_accepting_layers", None):
                logger.warning(
                    "progressive_layer_drop is enabled but no pipeline "
                    "layer accepts a layer_keep_prob kwarg — theta(t) "
                    "will be computed but unused")

        mode = ("spmd" if self._pipelined_protocol else
                "1f1b" if getattr(self, "_use_1f1b", False) else
                "sequential")
        log_dist(
            f"PipelineEngine: stages={self.num_stages}, "
            f"micro_batches={self.micro_batches}, mode={mode}",
            ranks=[0])

    def _virtual_stages_config(self):
        """pipeline.num_virtual_stages from the config block (validated
        int >= 1 by get_pipeline_config)."""
        return int((self._config.pipeline or {}).get(
            C.PIPELINE_NUM_VIRTUAL_STAGES,
            C.PIPELINE_NUM_VIRTUAL_STAGES_DEFAULT))

    # ------------------------------------------------------------------
    # model resolution: chain PipelineModule layers into one loss fn
    # ------------------------------------------------------------------
    def _resolve_model(self, model, model_parameters):
        if isinstance(model, PipelineModule):
            self.module = model
            det_accepting = _layers_accepting_deterministic(model)
            assert model_parameters is not None, (
                "PipelineModule requires explicit model_parameters "
                "(pass model_parameters=module.init_params(rng, example))")

            # Per-stage flat parameter storage (pipe/flat_params.py):
            # active exactly when the compiled 1F1B interpreter will run.
            # Parameters/grads/optimizer state then divide by the stage
            # count (ref module.py:197-249 builds only local layers per
            # process) — and by the model axis on top (the storage
            # composition of the reference's pipe×model grid, ref
            # topology.py:246-249); ZeRO param sharding (stage 3) is
            # capped at 2 — the pipe axis already partitions the
            # parameters.
            if self.mesh.shape[PIPE_AXIS] > 1 and \
                    self.gradient_accumulation_steps() == 1:
                # A 1-microbatch "pipeline" has no overlap and no 1F1B
                # memory partitioning — every pipe device would hold the
                # full model and idle (S-1)/S of the time. Refuse
                # loudly rather than degrade silently (VERDICT r4 #5).
                raise ValueError(
                    f"pipe={self.mesh.shape[PIPE_AXIS]} requires "
                    "gradient_accumulation_steps > 1: pipeline "
                    "parallelism overlaps MICROBATCHES across stages "
                    "(ref pipe/engine.py:59 train_batch consumes "
                    "micro_batches per step). Set "
                    '"gradient_accumulation_steps" >= the stage count '
                    "(2x stages recommended) in the config")
            self._pipe_flat_mode = (
                self.mesh.shape[PIPE_AXIS] > 1 and
                self.gradient_accumulation_steps() > 1)
            # the sequential (pipe=1) chain applies layers one at a
            # time — exactly the seam the ZeRO-3 gather scheduler
            # needs; flat 1F1B mode caps the stage at 2 instead (the
            # pipe axis already partitions parameters)
            self._zero3_chain_capable = not self._pipe_flat_mode
            self._pipe_virtual_stages = 1
            self._chunk_parts = None
            v_cfg = self._virtual_stages_config()
            if not self._pipe_flat_mode and v_cfg > 1:
                # refuse loudly rather than silently train uninterleaved
                # (the other interleave misconfigurations all raise) —
                # naming WHICH precondition failed
                raise ValueError(
                    f"pipeline.num_virtual_stages={v_cfg} requires the "
                    "compiled 1F1B executor, which needs a pipe mesh "
                    f"axis > 1 (got {self.mesh.shape[PIPE_AXIS]}) AND "
                    "gradient_accumulation_steps > 1 (got "
                    f"{self.gradient_accumulation_steps()}) — "
                    "interleaving has nothing to overlap on a "
                    "sequential layer chain")
            if self._pipe_flat_mode:
                assert model.num_stages == self.mesh.shape[PIPE_AXIS], (
                    f"PipelineModule was partitioned for "
                    f"{model.num_stages} stages but the mesh has "
                    f"pipe={self.mesh.shape[PIPE_AXIS]}; build the "
                    "module with num_stages matching the pipe axis")
                from jax.sharding import PartitionSpec
                from deepspeed_tpu.runtime.pipe.flat_params import \
                    StageFlatLayout
                # interleaved (virtual-stage) 1F1B: pipeline block's
                # num_virtual_stages splits the model into S*v chunks
                # assigned round-robin (chunk q on stage q % S), cutting
                # the fill/drain bubble toward 1/v (pipe/schedule.py
                # InterleavedTrainSchedule)
                S = self.mesh.shape[PIPE_AXIS]
                v = v_cfg
                stage_layers = None
                if v > 1:
                    gas = self.gradient_accumulation_steps()
                    if gas % S:
                        raise ValueError(
                            f"num_virtual_stages={v} requires "
                            f"gradient_accumulation_steps divisible by "
                            f"the stage count (microbatch groups of "
                            f"p): got gas={gas}, pipe={S}")
                    if len(model.layers) < S * v:
                        raise ValueError(
                            f"num_virtual_stages={v} needs at least "
                            f"stages*virtual = {S * v} layers to form "
                            f"chunks; the module has "
                            f"{len(model.layers)}")
                    self._pipe_virtual_stages = v
                    self._chunk_parts = model.partition(S * v)
                    # stage s stores chunks {s, s+S, ...}: the
                    # round-robin, non-contiguous layer set
                    stage_layers = [
                        [idx for j in range(v)
                         for idx in range(
                             self._chunk_parts[j * S + s],
                             self._chunk_parts[j * S + s + 1])]
                        for s in range(S)]
                # align so [S, F] divides over model (interp in_specs)
                # and the composed (model, data) master sharding
                self._pipe_layout = StageFlatLayout(
                    model, model_parameters,
                    align=self.mesh.shape[MODEL_AXIS] *
                    self.mesh.shape[DATA_AXIS],
                    stage_layers=stage_layers)
                model_parameters = self._pipe_layout.flatten(
                    model_parameters)
                self._zero_stage_cap = 2

                def _pipe_specs(params_f32):
                    flat, td = jax.tree_util.tree_flatten_with_path(
                        params_f32)
                    specs = [
                        PartitionSpec(PIPE_AXIS, MODEL_AXIS)
                        if jax.tree_util.keystr(path).startswith("['flat']")
                        else PartitionSpec()
                        for path, _ in flat]
                    return jax.tree_util.tree_unflatten(td, specs)

                self._param_specs_override = _pipe_specs

            kp_accepting = _layers_accepting(model, "layer_keep_prob")
            self._pld_accepting_layers = kp_accepting

            def _chained(params, batch, rngs, deterministic,
                         layer_keep_prob, collect):
                if getattr(self, "_pipe_flat_mode", False) and \
                        isinstance(params, dict) and "flat" in params:
                    params = self._pipe_layout.unflatten(params)
                inputs, labels = _split_batch(batch)
                x = inputs
                stats = [] if collect else None
                # ZeRO-3 runtime on the unrolled chain: each layer's
                # sharded params all-gather through the scheduler, with
                # the shared overlap fence (ops/overlap.py) tying layer
                # idx's gather to the activation entering layer
                # idx - prefetch_layers —
                # without the fence XLA may hoist every gather to the
                # top of the program (the naive up-front pattern);
                # backward reduce-scatters each layer's grad into its
                # owning shard via the gather's custom VJP
                sched = getattr(self, "zero3_scheduler", None)
                acts = [x]
                chain_bytes = []
                for idx in range(len(model.layers)):
                    kw = {}
                    if idx in det_accepting:
                        kw["deterministic"] = deterministic
                    if idx in kp_accepting and layer_keep_prob is not None:
                        # PLD θ(t): forwarded exactly as the base engine
                        # forwards it to monolithic models (ref
                        # engine.py:809-810 inherits through the pipe
                        # engine's forward)
                        kw["layer_keep_prob"] = layer_keep_prob
                    lp = model.layer_params(params, idx)
                    if sched is not None:
                        chain_bytes.append(sched.tree_gathered_nbytes(lp))
                        dep = acts[max(0, idx - sched.prefetch_layers)] \
                            if sched.release_after_use else None

                        def layer_call(lp_sharded, x, *, _idx=idx,
                                       _dep=dep, _kw=kw):
                            full = sched.gather(lp_sharded, depend=_dep)
                            return model.apply_layer(_idx, full, x,
                                                     rngs=rngs, **_kw)
                        if sched.release_after_use:
                            # remat the gather INSIDE the layer: the
                            # gathered copy would otherwise be an
                            # autodiff residual held from forward use
                            # until this layer's backward — O(L) live
                            # layers, not the window. Rematted, the
                            # residual is the SHARDED lp; backward
                            # re-gathers in reverse order, same as
                            # apply_layers' hand-written scan.
                            layer_call = jax.checkpoint(
                                layer_call, prevent_cse=False)
                        x = layer_call(lp, x)
                        acts.append(x)
                    else:
                        x = model.apply_layer(idx, lp, x, rngs=rngs,
                                              **kw)
                    if collect:
                        # numerics health: boundary stats AFTER layer
                        # idx — a finite input with a nonfinite output
                        # names the first-NaN layer
                        from deepspeed_tpu.monitor import numerics as nm
                        stats.append(nm.tensor_stats(x))
                if sched is not None:
                    sched.account_chain("pipe_chain", chain_bytes)
                if model.loss_fn is not None:
                    x = model.loss_fn(x, labels)
                if collect:
                    from deepspeed_tpu.monitor import numerics as nm
                    return x, nm.stack_act_stats(stats)
                return x

            def chained_loss(params, batch, rngs=None,
                             deterministic=False, layer_keep_prob=None,
                             **_):
                return _chained(params, batch, rngs, deterministic,
                                layer_keep_prob, collect=False)

            def chained_loss_health(params, batch, rngs=None,
                                    deterministic=False,
                                    layer_keep_prob=None, **_):
                return _chained(params, batch, rngs, deterministic,
                                layer_keep_prob, collect=True)

            self._loss_fn = chained_loss
            if self._numerics_on:
                self._loss_and_health_fn = chained_loss_health
                self._act_layer_names = [
                    f"layer{idx}:{type(layer).__name__}"
                    for idx, layer in enumerate(model.layers)]
            self._initial_params = model_parameters
            return

        if self._pipelined_protocol:
            if self._virtual_stages_config() > 1:
                raise ValueError(
                    "pipeline.num_virtual_stages applies to the "
                    "compiled 1F1B executor (PipelineModule); the "
                    "stacked-stage SPMD protocol (PipelinedGPT2) has "
                    "no virtual-stage schedule")
            # PipelinedGPT2-style protocol: bind the mesh into the loss
            # so activation buffers carry pipe shardings (the mesh is
            # built before model resolution in the base __init__).
            self.module = model
            self._loss_fn = functools.partial(model.loss_fn, mesh=self.mesh)
            if model_parameters is None and hasattr(model, "params"):
                model_parameters = model.params
            assert model_parameters is not None, \
                "model_parameters required for pipelined models"
            self._initial_params = model_parameters
            return

        super()._resolve_model(model, model_parameters)

    def _jit_gas(self):
        # the SPMD pipeline microbatches inside the compiled loss
        return 1 if self._pipelined_protocol else \
            self.gradient_accumulation_steps()

    def _microbatches_per_step(self):
        # samples/throughput accounting: the SPMD path consumes all
        # micro_batches in its single jitted step
        return self.micro_batches if self._pipelined_protocol else \
            super()._microbatches_per_step()

    # ------------------------------------------------------------------
    # compiled 1F1B execution for heterogeneous PipelineModules
    # ------------------------------------------------------------------
    def _build_step_fns(self):
        super()._build_step_fns()
        self._use_1f1b = self._is_pipe_module and \
            getattr(self, "_pipe_flat_mode", False)
        self._interp_fn = None
        if not self._use_1f1b:
            return

        def pipe_step(state, stacked_batch, rng, lr, keep_prob):
            lr = self._resolve_step_lr(state, lr)
            loss, grads = self._interp_fn(
                state.params, stacked_batch, rng, state.scale.loss_scale)
            # join the padded layout when ZeRO pads odd leaves (same as
            # _micro_grad's exit path)
            grads = self.zero_policy.encode(grads, self._zero_pad_plan)
            new_state, overflow, grad_norm, hgrad = \
                self._unscale_clip_and_update(state, lr, grads=grads)
            health = {"grad": hgrad, "act": None} \
                if self._numerics_on else None
            # arity parity with the base _fused_step_jit (no MoE
            # router stats on the 1F1B pipeline path)
            return new_state, loss, overflow, grad_norm, health, None

        # the base train_batch dispatches whatever _fused_step_jit is;
        # the 1F1B program replaces the sequential-chain scan
        self._fused_step_jit = jax.jit(pipe_step, donate_argnums=(0,))

    def _interp_example_mb(self, stacked_batch):
        dp = self.mesh.shape[DATA_AXIS]
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                (np.asarray(x).shape[1] // dp,) + np.asarray(x).shape[2:],
                np.asarray(x).dtype),
            stacked_batch)

    @staticmethod
    def _batch_sig(stacked_batch):
        return tuple(sorted(
            (jax.tree_util.keystr(p), np.asarray(l).shape,
             str(np.asarray(l).dtype))
            for p, l in jax.tree_util.tree_flatten_with_path(
                stacked_batch)[0]))

    def _ensure_interp(self, stacked_batch):
        """Lazy-build the compiled 1F1B step: boundary shapes come from
        the first batch (one LOCAL microbatch as seen inside shard_map:
        the per-microbatch batch dim divides over the data axis)."""
        if self._interp_fn is not None:
            # the compiled program bakes the boundary avals of the
            # first batch; silently padding a different shape would
            # corrupt the flat activation transport
            if self._batch_sig(stacked_batch) != self._interp_sig:
                raise ValueError(
                    "1F1B train batches must keep one shape; got "
                    f"{self._batch_sig(stacked_batch)} after compiling "
                    f"for {self._interp_sig}")
            return
        self._interp_sig = self._batch_sig(stacked_batch)
        # a multi-minute 1F1B compile is indistinguishable from a hang
        # without this: the stall diagnostic shows a fresh "compile"
        # heartbeat instead of a dead engine (interleaving multiplies
        # the schedule ticks by ~v and the lax.switch branch count by
        # v, so its compile is correspondingly longer — the same
        # warning applies, amplified)
        self.monitor.heartbeat("compile")
        from deepspeed_tpu.runtime.pipe.interp import build_pipeline_step
        v = getattr(self, "_pipe_virtual_stages", 1)
        self._interp_fn = build_pipeline_step(
            module=self.module, mesh=self.mesh,
            micro_batches=self.micro_batches,
            params_example=self.state.params,
            batch_example=self._interp_example_mb(stacked_batch),
            split_batch=_split_batch,
            det_accepting=_layers_accepting_deterministic(self.module),
            layout=getattr(self, "_pipe_layout", None),
            num_virtual_stages=v,
            chunk_parts=getattr(self, "_chunk_parts", None))
        bm = getattr(self._interp_fn, "buffer_meta", None)
        if bm:
            # memory ledger: the executor's persistent per-stage carry
            # (saved-input recompute buffers + delivery rings) — the
            # 1F1B activation bound, attributed so an OOM dump can tell
            # schedule memory from model state
            from deepspeed_tpu.monitor import memory as _mem
            self.monitor.ledger.register(
                _mem.CAT_PIPE, "pipe.1f1b_buffers",
                bm["bytes_per_stage"],
                meta={k: bm[k] for k in
                      ("saved_input_buffers", "channel_depth",
                       "flat_width", "transport_dtype")})
        log_dist(
            f"PipelineEngine: compiled "
            f"{'interleaved ' if v > 1 else ''}1F1B schedule over "
            f"{self.num_stages} stages"
            + (f" x {v} virtual" if v > 1 else "")
            + f", {self.micro_batches} microbatches (clock-aligned "
            f"{'InterleavedTrainSchedule' if v > 1 else 'TrainSchedule'}"
            ")", ranks=[0])

    def _ensure_eval_interp(self, stacked_batch):
        """Forward-only pipelined eval (the InferenceSchedule dataflow,
        ref schedule.py:86-127): overlapped stage execution with the
        2-buffer bound and no backward. Compiled per batch-shape (eval
        batches commonly vary, e.g. a final partial batch)."""
        sig = self._batch_sig(stacked_batch)
        cache = getattr(self, "_eval_interp_cache", None)
        if cache is None:
            cache = self._eval_interp_cache = {}
        if sig in cache:
            self._eval_interp_jit = cache.pop(sig)
            cache[sig] = self._eval_interp_jit  # LRU: re-insert as newest
            return
        # Bounded LRU: eval loops with varying trailing partial batches
        # would otherwise accumulate one full compiled 1F1B program per
        # distinct shape.
        while len(cache) >= self._EVAL_INTERP_CACHE_MAX:
            cache.pop(next(iter(cache)))
        from deepspeed_tpu.runtime.pipe.interp import build_pipeline_step
        eval_fn = build_pipeline_step(
            module=self.module, mesh=self.mesh,
            micro_batches=self.micro_batches,
            params_example=self.state.params,
            batch_example=self._interp_example_mb(stacked_batch),
            split_batch=_split_batch,
            det_accepting=_layers_accepting_deterministic(self.module),
            train=False, layout=getattr(self, "_pipe_layout", None),
            num_virtual_stages=getattr(self, "_pipe_virtual_stages", 1),
            chunk_parts=getattr(self, "_chunk_parts", None))
        self._eval_interp_jit = cache[sig] = jax.jit(eval_fn)

    # ------------------------------------------------------------------
    # batch API (ref pipe/engine.py:244,320)
    # ------------------------------------------------------------------
    def _collect_full_batch(self, data_iter=None, batch=None):
        """One global batch = micro_batches microbatches concatenated."""
        if batch is None:
            assert data_iter is not None
            micro = [next(data_iter) for _ in range(self.micro_batches)]
            batch = jax.tree_util.tree_map(
                lambda *xs: np.concatenate(
                    [np.asarray(x) for x in xs]), *micro)
        return batch

    def _train_batch_impl(self, data_iter=None, batch=None):
        """SPMD path: the microbatch axis folds *inside* the compiled
        loss, so the step sees one [1, full_batch, ...] stack.
        Sequential path: the full batch splits into [gas, micro_bs, ...]
        and the base engine's fused scan provides the microbatch loop.
        (The public train_batch is the base class's crash-guarded
        wrapper — an exception anywhere in here still dumps the flight
        recorder.)"""
        m = self.micro_batches
        batch = self._collect_full_batch(data_iter, batch)
        if self._pipelined_protocol:
            full = _to_dict_batch(batch)
            stacked = jax.tree_util.tree_map(lambda x: x[None], full)
        else:
            stacked = jax.tree_util.tree_map(
                lambda x: np.asarray(x).reshape(
                    (m, np.asarray(x).shape[0] // m) +
                    np.asarray(x).shape[1:]), batch)
            if getattr(self, "_use_1f1b", False):
                stacked = _to_dict_batch(stacked)
                self._ensure_interp(stacked)
        te = self.monitor.trace_export
        if te is not None and getattr(self, "_use_1f1b", False) and \
                self._interp_fn is not None:
            # per-microbatch pipeline timeline: the compiled schedule's
            # clock tables laid over this dispatch's REAL host wall
            # window (under async dispatch: enqueue time — the tick
            # layout, concurrency and bubble come from the tables, the
            # absolute placement from the host clock)
            import time as _time
            t0 = _time.perf_counter()
            loss = super()._train_batch_impl(batch=stacked)
            te.add_pipeline_step(
                self._interp_fn.clock_tables, self._interp_fn.pipe_meta,
                t0, _time.perf_counter(), step=self._host_steps)
            return loss
        return super()._train_batch_impl(batch=stacked)

    def eval_batch(self, data_iter=None, batch=None):
        # the SPMD pipelined loss consumes a full batch of micro_batches
        # microbatches — same collection as train_batch
        if self._pipelined_protocol:
            batch = self._collect_full_batch(data_iter, batch)
        elif getattr(self, "_use_1f1b", False):
            m = self.micro_batches
            batch = self._collect_full_batch(data_iter, batch)
            stacked = jax.tree_util.tree_map(
                lambda x: np.asarray(x).reshape(
                    (m, np.asarray(x).shape[0] // m) +
                    np.asarray(x).shape[1:]), _to_dict_batch(batch))
            self._ensure_eval_interp(stacked)
            return self._eval_interp_jit(
                self.state.params,
                jax.tree_util.tree_map(np.asarray, stacked),
                jax.random.PRNGKey(0), np.float32(1.0))
        elif batch is None and data_iter is not None:
            batch = next(data_iter)
        batch = _to_dict_batch(batch)
        return super().eval_batch(batch)

    # ------------------------------------------------------------------
    # stage predicates (ref pipe/engine.py; used by user code)
    # ------------------------------------------------------------------
    def is_first_stage(self):
        return self.grid.is_first_stage()

    def is_last_stage(self):
        return self.grid.is_last_stage()

    def is_gradient_accumulation_boundary(self):
        return True

    def set_dataiterator(self, iterator):
        self.data_iterator = iterator

    # -- stored-layout <-> logical-tree translation ---------------------
    @property
    def module_params(self):
        """Compute-dtype parameters as the module's LOGICAL tree
        (`{"layers", "tied"}`), regardless of the engine's stored
        layout (the flat-stage layout is an internal storage format)."""
        p = self.state.params
        if getattr(self, "_pipe_flat_mode", False):
            p = self._pipe_layout.unflatten(p)
        return p

    @property
    def fp32_params(self):
        p = DeepSpeedEngine.fp32_params.fget(self)
        if getattr(self, "_pipe_flat_mode", False):
            p = self._pipe_layout.unflatten(p)
        return p

    def _module_ckpt_template(self):
        if getattr(self, "_pipe_flat_mode", False):
            return self._pipe_layout.template(self.state.params)
        return super()._module_ckpt_template()

    def _logical_module_tree(self, stored):
        """Checkpoint-snapshot hook: the flat-stage layout unflattens
        into per-layer trees by slicing the SNAPSHOT buffers (async
        device ops — the save path stays sync-free), so the per-layer
        writer rides the same snapshot protocol as tree engines."""
        if getattr(self, "_pipe_flat_mode", False) and \
                isinstance(stored, dict) and "flat" in stored:
            return self._pipe_layout.unflatten(stored)
        return stored

    def _module_from_ckpt(self, tree):
        if getattr(self, "_pipe_flat_mode", False):
            return self._pipe_layout.flatten(tree)
        return tree

    def _count_model_params(self, tree):
        if getattr(self, "_pipe_flat_mode", False) and \
                isinstance(tree, dict) and "flat" in tree:
            return self._pipe_layout.num_params(tree)
        return super()._count_model_params(tree)

    def forward(self, *args, **kwargs):
        raise RuntimeError(
            "Only train_batch() / eval_batch() are accessible on the "
            "pipeline engine (ref pipe/engine.py:328-338)")

    def backward(self, *args, **kwargs):
        raise RuntimeError(
            "Only train_batch() / eval_batch() are accessible on the "
            "pipeline engine")

    def step(self, *args, **kwargs):
        raise RuntimeError(
            "Only train_batch() / eval_batch() are accessible on the "
            "pipeline engine")

    # schedule introspection (testing / multi-controller)
    def train_schedule(self):
        return TrainSchedule(micro_batches=self.micro_batches,
                             stages=self.num_stages,
                             stage_id=self.stage_id)


def _layers_accepting(model, kwarg):
    """Indices of layers whose __call__ takes the given kwarg."""
    accepting = set()
    for idx, layer in enumerate(model.layers):
        target = getattr(type(layer), "__call__", None) \
            if hasattr(layer, "apply") else layer
        try:
            if kwarg in inspect.signature(target).parameters:
                accepting.add(idx)
        except (TypeError, ValueError):
            pass
    return accepting


def _layers_accepting_deterministic(model):
    return _layers_accepting(model, "deterministic")


def _split_batch(batch):
    if isinstance(batch, (tuple, list)) and len(batch) == 2:
        return batch[0], batch[1]
    if isinstance(batch, dict):
        inputs = batch.get("inputs", batch.get("x", batch.get("input_ids")))
        labels = batch.get("labels", batch.get("y"))
        return inputs, labels
    return batch, None


def _to_dict_batch(batch):
    if isinstance(batch, (tuple, list)) and len(batch) == 2:
        return {"input_ids": np.asarray(batch[0]),
                "labels": np.asarray(batch[1])}
    return batch
