"""Compiled 1F1B executor for heterogeneous PipelineModules.

Counterpart of the reference's schedule interpreter
(`deepspeed/runtime/pipe/engine.py:1135-1161`: `_exec_schedule` walking
`_INSTRUCTION_MAP` with blocking p2p). The TPU-native form compiles the
SAME TrainSchedule instruction streams into one SPMD program:

  1. `build_clock_tables` interprets every stage's TrainSchedule stream
     with a FIFO one-slot channel model (send at tick t is receivable
     from tick t+1 — the compiled analogue of blocking p2p) into
     globally clock-aligned numpy tables: which stage runs which
     microbatch's forward/backward at every tick.
  2. `build_pipeline_step` lowers those tables to a `lax.scan` over
     ticks inside `shard_map` over the `pipe` mesh axis. Each pipe
     shard executes ITS stage's work via `lax.switch` (per-device
     divergent control flow — heterogeneous layers and activation
     shapes are handled by padding inter-stage activations to one flat
     f32 buffer), activations ride `ppermute(+1)` and cotangents
     `ppermute(-1)`.

Backward uses per-(microbatch, stage) recompute from the saved stage
INPUT activation (`jax.vjp` inside the backward branch), so the live
activation memory per stage is the schedule's buffer bound —
`TrainSchedule.num_pipe_buffers() = min(stages - stage + 1, m)` saved
inputs (ref `schedule.py:243-247`) — instead of GPipe's `m` full
per-layer residual sets. Stages genuinely overlap: at any steady-state
tick every pipe shard is executing a different microbatch.

Tied layers (TiedLayerSpec) appear in several stages; each shard
contributes its stage's grads and the final `psum` over the pipe axis
IS ReduceTiedGrads (ref `module.py:405-409`).

MEMORY: stage-exclusive parameters are stored in the per-stage flat
layout (`pipe/flat_params.py`) — one `[S, F]` buffer per dtype sharded
over the pipe axis, so each shard holds only its stage's params, grads
and optimizer state (the SPMD form of the reference building only
local layers per process, ref `module.py:197-249`); tied leaves stay
replicated with psum'd grads. Together with the schedule's
`num_pipe_buffers()` activation bound, pipe>1 divides both parameter
and activation memory by the stage count.

MODEL-AXIS COMPOSITION: with model>1 the [S, F] buffers shard over the
model axis too (each (pipe, model) shard stores F/model of its stage,
masters/moments compose (model, data) on top), the stage compute
all-gathers its stage over the model axis per tick and keeps only its
own grad segment — parameter/optimizer memory divides by pipe*model
(*data for masters), the storage composition of the reference's
pipe×model grid (`topology.py:246-249`). The gather is the ZeRO-3
pattern riding the shortest ICI hops (model is the innermost mesh
axis); split-matmul tensor parallelism inside a stage needs TP-aware
layers, which the homogeneous stacked-stage SPMD protocol provides.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.mesh import (DATA_AXIS, MODEL_AXIS, PIPE_AXIS,
                                        stacked_batch_pspecs)
from deepspeed_tpu.runtime.pipe.schedule import (
    TrainSchedule, InterleavedTrainSchedule, ForwardPass, BackwardPass,
    SendActivation, RecvActivation, SendGrad, RecvGrad, LoadMicroBatch,
    interleaved_fwd_cmds)


# ----------------------------------------------------------------------
# schedule -> clock tables
# ----------------------------------------------------------------------
def _inference_streams(m, S, v=1):
    """Canonical fwd-only streams with InferenceSchedule's dataflow
    (`schedule.py:86-127`). The literal InferenceSchedule emits
    SendActivation one step AFTER the producing ForwardPass (a
    host-runtime buffering detail); the compiled executor's send
    register holds exactly one tick, so the send is folded into the
    producing step — same dependency structure, same 2-buffer bound.

    v > 1: the interleaved forward order (microbatch groups of S,
    chunks round-robin — InterleavedTrainSchedule's fwd stream) with
    two alternating buffers per chunk."""
    n_chunks = S * v
    streams = []
    for s in range(S):
        steps = []
        if v == 1:
            order = [(0, mb) for mb in range(m)]
        else:
            sched = InterleavedTrainSchedule(m, S, s, v)
            order = [sched._fwd_cm(i) for i in range(m * v)]
        for vidx, mb in order:
            # two alternating eval buffers per chunk; the dataflow
            # itself comes from the schedule's single source of truth
            steps.append(interleaved_fwd_cmds(
                s, S, n_chunks, vidx, mb, vidx * 2 + mb % 2))
        streams.append(steps)
    return streams


def build_clock_tables(micro_batches, stages, train=True,
                       num_virtual_stages=1):
    """Align the per-stage schedule streams on a global clock
    (TrainSchedule / InterleavedTrainSchedule for num_virtual_stages>1,
    or the fwd-only InferenceSchedule dataflow when train=False).

    Each stage executes at most one schedule step per tick; a step is
    eligible when every RecvActivation/RecvGrad it contains pairs with
    a Send* completed at an EARLIER tick (k-th recv on a channel pairs
    with the k-th send — FIFO), and any Send* it contains has a free
    channel slot. Returns int/bool arrays indexed [tick, stage].

    Channels form a RING when interleaving (round-robin chunk q sends
    forward to stage (q+1) mod S — the last stage's non-final chunks
    wrap to stage 0); with one virtual stage the wrap channels are
    never used and the tables are bit-identical to before.  The
    fwd/bwd chunk rows carry the GLOBAL chunk id (vidx·S + s) the
    executor's lax.switch dispatches on, and the sent_act/sent_grad
    rows gate the executor's send registers so an op that does not
    send (e.g. the loss chunk on the last stage) cannot clobber an
    undelivered value."""
    m, S, v = micro_batches, stages, int(num_virtual_stages)
    if train:
        if v > 1:
            streams = [list(InterleavedTrainSchedule(m, S, s, v).steps())
                       for s in range(S)]
        else:
            streams = [list(TrainSchedule(m, S, s).steps())
                       for s in range(S)]
    else:
        streams = _inference_streams(m, S, v)

    # one-slot channels deadlock the interleaved ring (every stage's
    # warmup wants recv+fwd+send atomically while every channel holds
    # an undelivered value); depth-2 rings break the cycle for every
    # (m, S, v) swept — retry upward as a safety margin. v == 1 keeps
    # the single slot: tables (and the compiled program) stay identical
    # to the pre-interleaving executor.
    caps = (1,) if v == 1 else (2, 3, 4, 2 * v * S)
    tables = None
    for cap in caps:
        tables = _align_streams(streams, S, cap,
                                max_ticks=4 * (m * v + S) + 16)
        if tables is not None:
            break
    assert tables is not None, "clock alignment did not converge"
    return tables


def _align_streams(streams, S, cap, max_ticks):
    """Greedy clock alignment of per-stage instruction streams with
    `cap`-deep FIFO delivery rings per channel.  Returns the tick
    tables, or None if the streams deadlock at this capacity."""
    fwd_mb = []
    fwd_buf = []
    fwd_ch = []
    bwd_mb = []
    bwd_buf = []
    bwd_ch = []
    sent_act = []
    sent_grad = []
    recv_act_slot = []
    recv_grad_slot = []

    send_act_count = [0] * S
    recv_act_count = [0] * S
    send_grad_count = [0] * S
    recv_grad_count = [0] * S
    fwd_count = [0] * S
    bwd_count = [0] * S
    ptr = [0] * S
    t = 0
    while any(ptr[s] < len(streams[s]) for s in range(S)):
        if t >= max_ticks:
            return None
        f_row = [-1] * S
        fb_row = [0] * S
        fc_row = [0] * S
        b_row = [-1] * S
        bb_row = [0] * S
        bc_row = [0] * S
        sa_row = [False] * S
        sg_row = [False] * S
        ras_row = [-1] * S
        rgs_row = [-1] * S
        snap_sa = list(send_act_count)
        snap_sg = list(send_grad_count)
        snap_ra = list(recv_act_count)
        snap_rg = list(recv_grad_count)
        progressed = False
        for s in range(S):
            if ptr[s] >= len(streams[s]):
                continue
            cmds = streams[s][ptr[s]]
            ok = True
            for c in cmds:
                if isinstance(c, RecvActivation):
                    # k-th recv pairs with the k-th send (FIFO), which
                    # must have completed at an EARLIER tick
                    ok &= recv_act_count[s] < snap_sa[(s - 1) % S]
                elif isinstance(c, RecvGrad):
                    ok &= recv_grad_count[s] < snap_sg[(s + 1) % S]
                elif isinstance(c, SendActivation):
                    # ring depth: at most `cap` sends in flight
                    # (delivered-but-unconsumed) per channel
                    ok &= send_act_count[s] - snap_ra[(s + 1) % S] < cap
                elif isinstance(c, SendGrad):
                    ok &= send_grad_count[s] - snap_rg[(s - 1) % S] < cap
            if not ok:
                continue
            progressed = True
            for c in cmds:
                if isinstance(c, RecvActivation):
                    ras_row[s] = recv_act_count[s] % cap
                    recv_act_count[s] += 1
                elif isinstance(c, RecvGrad):
                    rgs_row[s] = recv_grad_count[s] % cap
                    recv_grad_count[s] += 1
                elif isinstance(c, SendActivation):
                    send_act_count[s] += 1
                    sa_row[s] = True
                elif isinstance(c, SendGrad):
                    send_grad_count[s] += 1
                    sg_row[s] = True
                elif isinstance(c, ForwardPass):
                    # the executor needs the MICROBATCH id (what the
                    # first/last chunks index the stacked batch with);
                    # plain schedules execute microbatches in order so
                    # the fwd ordinal doubles as the id, interleaved
                    # ops carry it explicitly
                    f_row[s] = getattr(c, "mb", fwd_count[s])
                    fb_row[s] = c.buffer_id
                    fc_row[s] = getattr(c, "chunk", 0) * S + s
                    fwd_count[s] += 1
                elif isinstance(c, BackwardPass):
                    b_row[s] = getattr(c, "mb", bwd_count[s])
                    bb_row[s] = c.buffer_id
                    bc_row[s] = getattr(c, "chunk", 0) * S + s
                    bwd_count[s] += 1
            ptr[s] += 1
        fwd_mb.append(f_row)
        fwd_buf.append(fb_row)
        fwd_ch.append(fc_row)
        bwd_mb.append(b_row)
        bwd_buf.append(bb_row)
        bwd_ch.append(bc_row)
        sent_act.append(sa_row)
        sent_grad.append(sg_row)
        recv_act_slot.append(ras_row)
        recv_grad_slot.append(rgs_row)
        t += 1
        if not progressed:
            return None

    T = t
    sent_act = np.asarray(sent_act, bool)
    sent_grad = np.asarray(sent_grad, bool)
    # delivery at tick t = what the ring neighbor sent at tick t-1
    # (acts travel +1 mod S, grads -1 mod S; the wrap columns are
    # all-False when v == 1).  The k-th delivery lands in ring slot
    # k % cap — the slot the k-th recv reads.
    deliver_act = np.zeros((T, S), bool)
    deliver_act[1:] = np.roll(sent_act[:-1], 1, axis=1)
    deliver_grad = np.zeros((T, S), bool)
    deliver_grad[1:] = np.roll(sent_grad[:-1], -1, axis=1)
    deliver_act_slot = np.full((T, S), -1, np.int32)
    deliver_grad_slot = np.full((T, S), -1, np.int32)
    dcount_a = np.zeros(S, np.int64)
    dcount_g = np.zeros(S, np.int64)
    for tick in range(T):
        for s in range(S):
            if deliver_act[tick, s]:
                deliver_act_slot[tick, s] = dcount_a[s] % cap
                dcount_a[s] += 1
            if deliver_grad[tick, s]:
                deliver_grad_slot[tick, s] = dcount_g[s] % cap
                dcount_g[s] += 1
    return {
        "fwd_mb": np.asarray(fwd_mb, np.int32),
        "fwd_buf": np.asarray(fwd_buf, np.int32),
        "fwd_chunk": np.asarray(fwd_ch, np.int32),
        "bwd_mb": np.asarray(bwd_mb, np.int32),
        "bwd_buf": np.asarray(bwd_buf, np.int32),
        "bwd_chunk": np.asarray(bwd_ch, np.int32),
        "sent_act": sent_act,
        "sent_grad": sent_grad,
        "deliver_act": deliver_act,
        "deliver_grad": deliver_grad,
        "deliver_act_slot": deliver_act_slot,
        "deliver_grad_slot": deliver_grad_slot,
        "recv_act_slot": np.asarray(recv_act_slot, np.int32),
        "recv_grad_slot": np.asarray(recv_grad_slot, np.int32),
        "channel_depth": cap,
        "num_ticks": T,
    }


def num_pipe_buffers(micro_batches, stages, num_virtual_stages=1):
    """Global buffer-array bound: the worst stage's
    num_pipe_buffers() (plain 1F1B stage 0: min(stages+1, m))."""
    if num_virtual_stages > 1:
        return max(InterleavedTrainSchedule(
            micro_batches, stages, s, num_virtual_stages)
            .num_pipe_buffers() for s in range(stages))
    return max(TrainSchedule(micro_batches, stages, s).num_pipe_buffers()
               for s in range(stages))


# ----------------------------------------------------------------------
# stage function construction
# ----------------------------------------------------------------------
def _microbatch(tree, mb):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, mb, 0, keepdims=False),
        tree)


def build_pipeline_step(module, mesh, micro_batches, params_example,
                        batch_example, split_batch, det_accepting,
                        train=True, layout=None, num_virtual_stages=1,
                        chunk_parts=None):
    """Compile-time construction of the pipelined step function:
    `(params, stacked_batch, rng, loss_scale) -> (loss, grads)` for
    train=True (1F1B), or `... -> loss` for train=False (the fwd-only
    InferenceSchedule dataflow — no saved buffers, no backward).

    params_example/batch_example: concrete or ShapeDtypeStruct pytrees
    used only for shape inference (batch_example is ONE microbatch).
    split_batch: callable batch -> (inputs, labels).

    layout (StageFlatLayout): when given, `params` is the per-stage
    flat storage `{"flat": {dt: [S, F]}, "tied": tree}` sharded over
    the pipe axis — each shard slices ITS stage's params out of its
    local [1, F] view (the SPMD form of the reference building only
    local layers per process, ref module.py:197-249), and gradients
    come back in the same layout (flat [S, F] per dtype + replicated
    tied tree). Without it, params are a replicated full tree.

    num_virtual_stages > 1 compiles the INTERLEAVED 1F1B schedule
    (InterleavedTrainSchedule): the model is split into S·v chunks
    (`chunk_parts`, a parts list of length S·v+1) assigned round-robin
    (chunk q on stage q mod S), every tick's lax.switch dispatches on
    the GLOBAL chunk id, activations/cotangents ride a closed ppermute
    ring with depth-`channel_depth` FIFO delivery slots, and the
    fill/drain bubble shrinks from (S-1)/(m+S-1) stage-times toward
    (S-1)/(v·m+S-1).  v == 1 compiles the exact pre-interleaving
    program (chain permutes, single delivery slot)."""
    S = mesh.shape[PIPE_AXIS]
    M = mesh.shape[MODEL_AXIS]
    m = micro_batches
    v = int(num_virtual_stages)
    n_chunks = S * v
    tables = dict(build_clock_tables(m, S, train=train,
                                     num_virtual_stages=v))
    # kept (numpy) for the trace exporter: the compiled program's
    # EXACT per-tick (stage, microbatch, chunk) placement, stamped
    # with host dispatch windows by pipe/engine.py
    export_tables = dict(tables)
    C = int(tables.pop("channel_depth"))
    B = num_pipe_buffers(m, S, v) if train else 2 * v
    parts = list(module.parts) if chunk_parts is None else \
        list(chunk_parts)
    assert len(parts) == n_chunks + 1, (
        f"chunk parts length {len(parts)} != stages*virtual+1 = "
        f"{n_chunks + 1}")

    inputs_ex, labels_ex = split_batch(batch_example)

    def run_chunk(q, params, x, rng, deterministic):
        start, stop = parts[q], parts[q + 1]
        for idx in range(start, stop):
            kw = {}
            if idx in det_accepting:
                kw["deterministic"] = deterministic
            x = module.apply_layer(
                idx, module.layer_params(params, idx), x,
                rngs={"dropout": rng} if rng is not None else None, **kw)
        return x

    # -- param carrier: what the backward differentiates against ------
    # legacy: the (replicated) full tree itself.  flat layout: the
    # shard-local flat buffers + the tied tree; `params_of` rebuilds a
    # stage-sufficient {"layers", "tied"} dict from either.  A chunk's
    # layers live in its OWNER stage's segment (round-robin: stage
    # q mod S), which is exactly the local shard wherever the chunk's
    # switch branch actually executes.
    if layout is None:
        def carrier_of(params):
            return params

        def params_of(s, carrier):
            return carrier

        def local_grads(dcarrier):
            return dcarrier
    else:
        for dt in layout.F:
            assert layout.F[dt] % M == 0, (
                f"flat buffer width {layout.F[dt]} ({dt}) not divisible "
                f"by model={M}; build StageFlatLayout with "
                "align=model*data (the engine's setting — model alone "
                "satisfies this assert but leaves masters unshardable "
                "over data)")

        def carrier_of(params):
            # model>1 divides stage parameter STORAGE over the model
            # axis (each (pipe, model) shard holds F/model of its
            # stage); the stage compute gathers the full stage and runs
            # replicated within each TP group — the storage composition
            # of the reference's pipe×model grid (ref topology.py:
            # 246-249; true split-matmul TP needs TP-aware layers, which
            # the stacked-stage SPMD protocol provides).
            return ({dt: jax.lax.all_gather(
                        params["flat"][dt][0], MODEL_AXIS,
                        axis=0, tiled=True)
                     for dt in layout.F},
                    params.get("tied", {}))

        def params_of(s, carrier):
            flat_local, tied = carrier
            return {"layers": layout.unflatten_stage(s, flat_local),
                    "tied": tied}

        def local_grads(dcarrier):
            # the gathered-carrier cotangent is the FULL stage grad,
            # identical on every model shard (replicated compute, same
            # data shard) — each shard keeps only its own segment so the
            # accumulated grads come back already model-partitioned
            dflat, dtied = dcarrier
            i = jax.lax.axis_index(MODEL_AXIS)
            dflat = {dt: jax.lax.dynamic_slice_in_dim(
                         dflat[dt], i * (layout.F[dt] // M),
                         layout.F[dt] // M)
                     for dt in layout.F}
            return dflat, dtied

    # boundary avals: activation entering chunk q (q >= 1); shape
    # inference runs on the logical full tree regardless of storage
    full_example = params_example if layout is None else \
        jax.eval_shape(layout.unflatten, params_example)
    bnd = []
    x_aval = jax.eval_shape(lambda x: x, inputs_ex)
    for q in range(n_chunks):
        x_aval = jax.eval_shape(
            functools.partial(run_chunk, q, deterministic=True, rng=None),
            full_example, x_aval)
        bnd.append(x_aval)
    # bnd[q] = output of chunk q = input of chunk q+1
    in_avals = [jax.eval_shape(lambda x: x, inputs_ex)] + bnd[:-1]
    flat_sizes = [
        sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(a))
        for a in bnd[:-1]]
    A = max(flat_sizes) if flat_sizes else 1
    # transport dtype for the flat activation/cotangent buffers: the
    # boundaries' common float dtype (bf16 models move half the pipe
    # bytes); any non-float leaf (e.g. ids threaded through) forces f32
    bleaves = [l for a in bnd[:-1] for l in jax.tree_util.tree_leaves(a)]
    if bleaves and all(jnp.issubdtype(l.dtype, jnp.floating)
                       for l in bleaves):
        tdt = jnp.result_type(*[l.dtype for l in bleaves])
    else:
        tdt = jnp.float32

    def to_flat(tree):
        leaves = [l.reshape(-1).astype(tdt)
                  for l in jax.tree_util.tree_leaves(tree)]
        flat = jnp.concatenate(leaves) if leaves else jnp.zeros((0,), tdt)
        return jnp.pad(flat, (0, A - flat.shape[0]))

    def from_flat(flat, aval):
        out = []
        off = 0
        leaves, treedef = jax.tree_util.tree_flatten(aval)
        for l in leaves:
            n = int(np.prod(l.shape))
            out.append(flat[off:off + n].reshape(l.shape).astype(l.dtype))
            off += n
        return jax.tree_util.tree_unflatten(treedef, out)

    def chunk_input(q, flat, batch, mb):
        if q == 0:
            inputs, _ = split_batch(batch)
            return _microbatch(inputs, mb)
        return from_flat(flat, in_avals[q])

    def fwd_fn(q):
        def fn(params, act_in, batch, mb, rng, loss_scale):
            x = chunk_input(q, act_in, batch, mb)
            r = jax.random.fold_in(jax.random.fold_in(rng, mb), q)
            y = run_chunk(q, params_of(q % S, carrier_of(params)), x, r,
                          deterministic=not train)
            if q == n_chunks - 1:
                _, labels = split_batch(batch)
                loss = module.loss_fn(y, _microbatch(labels, mb)) \
                    if module.loss_fn is not None else y
                return jnp.zeros((A,), tdt), \
                    loss.astype(jnp.float32)
            return to_flat(y), jnp.float32(0.0)
        return fn

    def _grads_f32(dcarrier):
        return jax.tree_util.tree_map(
            lambda g_: g_.astype(jnp.float32), dcarrier)

    def bwd_fn(q):
        def fn(params, x_saved_flat, grad_in, batch, mb, rng,
               loss_scale):
            x = chunk_input(q, x_saved_flat, batch, mb)
            r = jax.random.fold_in(jax.random.fold_in(rng, mb), q)
            carrier = carrier_of(params)

            if q == n_chunks - 1:
                def g(c, xx):
                    y = run_chunk(q, params_of(q % S, c), xx, r,
                                  deterministic=False)
                    _, labels = split_batch(batch)
                    loss = module.loss_fn(y, _microbatch(labels, mb)) \
                        if module.loss_fn is not None else y
                    return loss.astype(jnp.float32)
                cot = loss_scale / m
            else:
                def g(c, xx):
                    return run_chunk(q, params_of(q % S, c), xx, r,
                                     deterministic=False)
                cot = from_flat(grad_in, bnd[q])

            if q == 0:
                _, vjp = jax.vjp(lambda c: g(c, x), carrier)
                (dcarrier,) = vjp(cot)
                dx_flat = jnp.zeros((A,), tdt)
            else:
                _, vjp = jax.vjp(g, carrier, x)
                dcarrier, dx = vjp(cot)
                dx_flat = to_flat(dx)
            return dx_flat, _grads_f32(dcarrier)
        return fn

    fwd_fns = [fwd_fn(q) for q in range(n_chunks)]
    bwd_fns = [bwd_fn(q) for q in range(n_chunks)] if train else []

    # acts travel +1, cotangents -1; interleaving closes the ring (the
    # last stage's non-final chunks feed stage 0)
    if v > 1:
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [((i + 1) % S, i) for i in range(S)]
    else:
        fwd_perm = [(i, i + 1) for i in range(S - 1)]
        bwd_perm = [(i + 1, i) for i in range(S - 1)]

    rows = {k: jnp.asarray(val) for k, val in tables.items()
            if k != "num_ticks"}

    def _ring_write(ring, value, slot):
        upd = jax.lax.dynamic_update_index_in_dim(
            ring, value, jnp.maximum(slot, 0), 0)
        return jnp.where(slot >= 0, upd, ring)

    def _ring_read(ring, slot):
        return jax.lax.dynamic_index_in_dim(
            ring, jnp.maximum(slot, 0), 0, keepdims=False)

    def local_step(params, stacked_batch, rng, loss_scale):
        s = jax.lax.axis_index(PIPE_AXIS)
        dp = mesh.shape[DATA_AXIS]
        # decorrelate dropout across data shards (chunk folding happens
        # per-branch in fwd_fn/bwd_fn; fwd and recompute share the key)
        rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA_AXIS))

        if not train:
            # minimal carry: no grads tree, no backward registers or
            # saved-input buffers, no backward ppermute per tick
            def tick_eval(carry, row):
                act_ring, fwd_out, loss_sum = carry
                perm_act = jax.lax.ppermute(fwd_out, PIPE_AXIS, fwd_perm)
                act_ring = _ring_write(act_ring, perm_act,
                                       row["deliver_act_slot"][s])
                my_fwd = row["fwd_mb"][s]
                my_chunk = row["fwd_chunk"][s]
                x_in = _ring_read(act_ring, row["recv_act_slot"][s])

                def do_fwd(_):
                    return jax.lax.switch(
                        my_chunk, fwd_fns, params, x_in, stacked_batch,
                        my_fwd, rng, loss_scale)

                def no_fwd(_):
                    return fwd_out, jnp.float32(0.0)

                new_fwd_out, loss_inc = jax.lax.cond(
                    my_fwd >= 0, do_fwd, no_fwd, None)
                # only a sending op may occupy the send register (the
                # loss chunk's output must not clobber an undelivered
                # value riding the same register)
                fwd_next = jnp.where(row["sent_act"][s], new_fwd_out,
                                     fwd_out)
                return (act_ring, fwd_next, loss_sum + loss_inc), None

            carry, _ = jax.lax.scan(
                tick_eval,
                (jnp.zeros((C, A), tdt),
                 jnp.zeros((A,), tdt), jnp.float32(0.0)),
                rows)
            loss = jax.lax.psum(carry[2], PIPE_AXIS) / m
            if dp > 1:
                loss = jax.lax.pmean(loss, DATA_AXIS)
            return loss

        # grads carry mirrors the ACCUMULATED layout: full tree (legacy)
        # or (model-sliced flat buffers, tied tree) under the flat
        # layout (shapes only — the gather/slice chain is dead code XLA
        # eliminates)
        zeros_grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32),
            local_grads(carrier_of(params)))

        def tick(carry, row):
            (act_ring, grad_ring, fwd_out, grad_out, bufs, loss_sum,
             grads_acc) = carry
            # communication phase: deliver last tick's sends into their
            # FIFO ring slots
            perm_act = jax.lax.ppermute(fwd_out, PIPE_AXIS, fwd_perm)
            perm_grad = jax.lax.ppermute(grad_out, PIPE_AXIS, bwd_perm)
            act_ring = _ring_write(act_ring, perm_act,
                                   row["deliver_act_slot"][s])
            grad_ring = _ring_write(grad_ring, perm_grad,
                                    row["deliver_grad_slot"][s])

            my_fwd = row["fwd_mb"][s]
            my_fbuf = row["fwd_buf"][s]
            my_fchunk = row["fwd_chunk"][s]
            my_bwd = row["bwd_mb"][s]
            my_bbuf = row["bwd_buf"][s]
            my_bchunk = row["bwd_chunk"][s]
            x_in = _ring_read(act_ring, row["recv_act_slot"][s])

            def do_fwd(_):
                out, loss = jax.lax.switch(
                    my_fchunk, fwd_fns, params, x_in, stacked_batch,
                    my_fwd, rng, loss_scale)
                return out, loss

            def no_fwd(_):
                return fwd_out, jnp.float32(0.0)

            new_fwd_out, loss_inc = jax.lax.cond(my_fwd >= 0, do_fwd,
                                                 no_fwd, None)
            loss_sum = loss_sum + loss_inc
            # only a sending op occupies the send register — an op with
            # no SendActivation (the loss chunk) must not clobber a
            # value still riding toward its delivery
            fwd_next = jnp.where(row["sent_act"][s], new_fwd_out,
                                 fwd_out)
            # save the chunk-INPUT activation for backward recompute
            bufs = jnp.where(
                my_fwd >= 0,
                jax.lax.dynamic_update_index_in_dim(
                    bufs, x_in, my_fbuf, 0),
                bufs)

            def do_bwd(_):
                x_saved = jax.lax.dynamic_index_in_dim(
                    bufs, my_bbuf, 0, keepdims=False)
                g_in = _ring_read(grad_ring, row["recv_grad_slot"][s])
                dx, dparams = jax.lax.switch(
                    my_bchunk, bwd_fns, params, x_saved, g_in,
                    stacked_batch, my_bwd, rng, loss_scale)
                return dx, local_grads(dparams)

            def no_bwd(_):
                return grad_out, zeros_grads

            new_grad_out, dparams = jax.lax.cond(my_bwd >= 0, do_bwd,
                                                 no_bwd, None)
            grad_next = jnp.where(row["sent_grad"][s], new_grad_out,
                                  grad_out)
            grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc,
                                               dparams)
            return (act_ring, grad_ring, fwd_next, grad_next,
                    bufs, loss_sum, grads_acc), None

        init = (jnp.zeros((C, A), tdt),  # act delivery ring
                jnp.zeros((C, A), tdt),  # grad delivery ring
                jnp.zeros((A,), tdt),    # fwd_out (send register)
                jnp.zeros((A,), tdt),    # grad_out (send register)
                jnp.zeros((B, A), tdt),  # saved chunk inputs
                jnp.float32(0.0), zeros_grads)
        carry, _ = jax.lax.scan(tick, init, rows)
        loss_sum = carry[5]
        loss = jax.lax.psum(loss_sum, PIPE_AXIS) / m
        if dp > 1:
            loss = jax.lax.pmean(loss, DATA_AXIS)
        if layout is None:
            # ReduceGrads + ReduceTiedGrads: stage-disjoint leaves psum
            # to their single producer's value; tied leaves SUM across
            # stages
            grads = jax.tree_util.tree_map(
                lambda g_: jax.lax.psum(g_, PIPE_AXIS), carry[6])
            if dp > 1:
                grads = jax.tree_util.tree_map(
                    lambda g_: jax.lax.pmean(g_, DATA_AXIS), grads)
        else:
            # flat grads STAY stage-partitioned (each shard produced
            # only its stage's segment — no psum, the stacked [S, F]
            # output is the partitioned gradient store); tied grads SUM
            # across their user stages (ReduceTiedGrads)
            flat_g, tied_g = carry[6]
            tied_g = jax.tree_util.tree_map(
                lambda g_: jax.lax.psum(g_, PIPE_AXIS), tied_g)
            if dp > 1:
                flat_g = jax.tree_util.tree_map(
                    lambda g_: jax.lax.pmean(g_, DATA_AXIS), flat_g)
                tied_g = jax.tree_util.tree_map(
                    lambda g_: jax.lax.pmean(g_, DATA_AXIS), tied_g)
            grads = {"flat": {dt: flat_g[dt][None] for dt in layout.F},
                     "tied": tied_g}
        return loss, grads

    if layout is None:
        params_spec = P()
        grads_out_spec = P()
    else:
        # dim 1 over the model axis (size-1 model: identical to the
        # pipe-only spec); each (pipe, model) shard enters with its
        # [1, F/model] slice and leaves its own grad segment
        params_spec = {"flat": {dt: P(PIPE_AXIS, MODEL_AXIS)
                                for dt in layout.F},
                       "tied": P()}
        grads_out_spec = {"flat": {dt: P(PIPE_AXIS, MODEL_AXIS)
                                   for dt in layout.F},
                          "tied": P()}

    def step(params, stacked_batch, rng, loss_scale):
        b_specs = stacked_batch_pspecs(stacked_batch)
        return shard_map(
            local_step, mesh=mesh,
            in_specs=(params_spec, b_specs, P(), P()),
            out_specs=(P(), grads_out_spec) if train else P(),
            check_vma=False)(params, stacked_batch, rng, loss_scale)

    # forensics: the schedule this program executes (trace_export lays
    # these ticks over each dispatch's wall window)
    step.clock_tables = export_tables
    step.pipe_meta = {"stages": S, "micro_batches": m,
                      "num_virtual_stages": v, "train": train}
    # memory-ledger accounting of the executor's persistent per-stage
    # carry: saved-input recompute buffers [B, A] + the two depth-C
    # delivery rings + the fwd/bwd send registers, all in the flat
    # transport dtype. Per DEVICE (each pipe shard carries its own).
    _itemsize = jnp.dtype(tdt).itemsize
    step.buffer_meta = {
        "saved_input_buffers": int(B),
        "channel_depth": int(C),
        "flat_width": int(A),
        "transport_dtype": str(jnp.dtype(tdt).name),
        "bytes_per_stage": int(
            (B + 2 * C + 2 if train else C + 1) * A * _itemsize),
    }
    return step
