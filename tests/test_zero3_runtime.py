"""ZeRO-3 overlapped runtime (ISSUE 9): the explicit gather/release
scheduler (`runtime/zero/stage3.py`) — layer-granular all-gather
prefetched ahead of use, release after fwd/bwd use, reduce-scatter of
gradients into the owning data-axis shard.

What these tests pin:
  * the scheduled apply path computes the SAME function as the plain
    module path — bit-exact loss on identical sharded inputs, grads to
    float roundoff — for GPT-2 and BERT, across prefetch_layers
    settings and the naive up-front baseline;
  * a stage-3 engine's 10-step fp32 training trajectory matches a
    stage-2 engine's (same data, same init) to float roundoff;
  * stage-3 sharded checkpoints round-trip, including reload at a
    DIFFERENT prefetch_layers (the schedule is a trace-time choice,
    not state);
  * the hot loop stays sync-free with the scheduler on (the
    async-dispatch guard, re-run over the scheduled step);
  * the memory ledger's zero3_gather entry obeys the
    (prefetch_layers + 1)-layer bound, and the naive mode records the
    whole stack;
  * the sequential PipelineModule chain and the ZeRO-Offload
    compressed wire compose with the scheduler;
  * config validation raises ValueError carrying the offending value.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config
from deepspeed_tpu.runtime.mesh import build_mesh
from deepspeed_tpu.runtime.zero.partition import ZeroShardingPolicy
from deepspeed_tpu.runtime.zero.stage3 import (Zero3GatherScheduler,
                                               resolve_gather_dtype)


def _mesh():
    return build_mesh({"pipe": 1, "data": len(jax.devices()), "model": 1})


def _gpt2_batch(seed, rows=8, t=32, vocab=256, stacked=False):
    ids = np.random.default_rng(seed).integers(
        0, vocab, (rows, t)).astype(np.int32)
    return {"input_ids": ids[None] if stacked else ids}


def _engine_config(stage, stage3=None, **over):
    zo = {"stage": stage}
    if stage3 is not None:
        zo["stage3"] = stage3
    cfg = {"train_micro_batch_size_per_gpu": 8,
           "gradient_accumulation_steps": 1,
           "steps_per_print": 10000,
           "zero_optimization": zo,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}}
    cfg.update(over)
    return cfg


def _build_gpt2_engine(stage, stage3=None, n_layer=4, **over):
    model = GPT2ForCausalLM(tiny_gpt2_config(n_layer=n_layer))
    params = model.init(jax.random.PRNGKey(0), _gpt2_batch(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config=_engine_config(stage, stage3, **over))
    return engine, model


def _run(engine, steps, t=32):
    losses = []
    for i in range(steps):
        loss = engine.train_batch(batch=_gpt2_batch(i, t=t, stacked=True))
        losses.append(float(jax.device_get(loss)))
    return np.asarray(losses)


# ----------------------------------------------------------------------
# scheduled path == module path (fixed sharding, strongest invariant)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("prefetch,release", [(1, True), (0, True),
                                              (3, True), (1, False)])
def test_gpt2_scheduled_path_matches_module_path(prefetch, release):
    """Same sharded params + batch through the module path and the
    scheduled path: loss is BIT-EXACT, grads agree to float roundoff
    (the per-layer vjp + reduce-scatter accumulation is a different —
    equally valid — summation program)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    mesh = _mesh()
    model = GPT2ForCausalLM(tiny_gpt2_config(n_layer=4))
    batch = _gpt2_batch(7)
    params = model.init(jax.random.PRNGKey(0), batch)
    params = jax.device_put(
        params, ZeroShardingPolicy(mesh, 3).param_shardings(params))
    batch = jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, PartitionSpec("data", None))), batch)

    def loss(p, b):
        return model.loss_fn(p, b, rngs=None, deterministic=True)

    l0, g0 = jax.jit(jax.value_and_grad(loss))(params, batch)
    model.bind_zero3_scheduler(Zero3GatherScheduler(
        mesh, prefetch_layers=prefetch, release_after_use=release))
    l1, g1 = jax.jit(jax.value_and_grad(loss))(params, batch)
    model.bind_zero3_scheduler(None)

    assert np.array_equal(np.asarray(l0), np.asarray(l1)), (l0, l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=2e-6)


def test_bert_scheduled_path_matches_module_path():
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    from deepspeed_tpu.models.bert import (BertForPreTrainingLM,
                                           tiny_bert_config)
    mesh = _mesh()
    model = BertForPreTrainingLM(tiny_bert_config(num_hidden_layers=3))
    rng = np.random.default_rng(3)
    batch = {"input_ids": rng.integers(0, 256, (8, 32)).astype(np.int32),
             "attention_mask": np.ones((8, 32), np.int32),
             "masked_lm_labels": rng.integers(
                 0, 256, (8, 32)).astype(np.int32),
             "next_sentence_label": rng.integers(
                 0, 2, (8,)).astype(np.int32)}
    params = model.init(jax.random.PRNGKey(0), batch)
    params = jax.device_put(
        params, ZeroShardingPolicy(mesh, 3).param_shardings(params))
    batch = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(
            mesh, PartitionSpec(*(["data"] + [None] * (x.ndim - 1))))),
        batch)

    def loss(p, b):
        return model.loss_fn(p, b, rngs=None, deterministic=True)

    l0, g0 = jax.jit(jax.value_and_grad(loss))(params, batch)
    model.bind_zero3_scheduler(Zero3GatherScheduler(mesh))
    l1, g1 = jax.jit(jax.value_and_grad(loss))(params, batch)
    model.bind_zero3_scheduler(None)
    assert np.array_equal(np.asarray(l0), np.asarray(l1)), (l0, l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=5e-6)


# ----------------------------------------------------------------------
# stage 3 vs stage 2: fp32 10-step training trajectory
# ----------------------------------------------------------------------
def test_stage3_vs_stage2_fp32_loss_parity_10_steps():
    """The satellite acceptance run: an fp32 stage-3 engine (scheduled
    gathers, reduce-scattered grads, sharded params) tracks an fp32
    stage-2 engine bit-for-bit up to float roundoff over 10 optimizer
    steps on the same data. The two engines compile DIFFERENT XLA
    programs whose cross-shard reduction orders differ, so the bound
    is float-roundoff-tight (measured ~5e-7 absolute on a ~5.5 loss),
    not literal bit equality."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    e2, _ = _build_gpt2_engine(2)
    e3, _ = _build_gpt2_engine(3)
    assert e3.zero3_scheduler is not None, \
        "stage-3 engine did not weave the gather scheduler"
    assert e2.zero3_scheduler is None
    l2 = _run(e2, 10)
    l3 = _run(e3, 10)
    np.testing.assert_allclose(l3, l2, rtol=0, atol=5e-6)
    # and training actually progressed identically enough to converge
    # together: final params agree to roundoff
    for a, b in zip(jax.tree_util.tree_leaves(e2.fp32_params),
                    jax.tree_util.tree_leaves(e3.fp32_params)):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(jax.device_get(b)),
                                   rtol=0, atol=1e-5)


# ----------------------------------------------------------------------
# sharded checkpoint round-trip (incl. different prefetch_layers)
# ----------------------------------------------------------------------
_ROUNDTRIP_CHILD = r"""
import jax, numpy as np, sys, tempfile
import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config


def build(stage3=None):
    model = GPT2ForCausalLM(tiny_gpt2_config(n_layer=4))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((8, 32), np.int32)})
    zo = {"stage": 3}
    if stage3:
        zo["stage3"] = stage3
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 8,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 10000,
                "zero_optimization": zo,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    return engine


def batch(i):
    ids = np.random.default_rng(i).integers(
        0, 256, (1, 8, 32)).astype(np.int32)
    return {"input_ids": ids}


def run(engine, rng):
    return [float(jax.device_get(engine.train_batch(batch=batch(i))))
            for i in rng]


ref_losses = np.asarray(run(build(), range(6)))
ckpt_dir = tempfile.mkdtemp(prefix="zero3_roundtrip_")
e_a = build()
run(e_a, range(3))
e_a.save_checkpoint(ckpt_dir, tag="s3")
e_a.wait_for_checkpoint()

for stage3 in ({"prefetch_layers": 2}, {"release_after_use": False}):
    e_b = build(stage3)
    assert e_b.zero3_scheduler is not None
    e_b.load_checkpoint(ckpt_dir, tag="s3")
    for a, b in zip(jax.tree_util.tree_leaves(e_a.state.params),
                    jax.tree_util.tree_leaves(e_b.state.params)):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                      np.asarray(jax.device_get(b)))
    resumed = np.asarray(run(e_b, range(3, 6)))
    np.testing.assert_allclose(resumed, ref_losses[3:], rtol=0,
                               atol=5e-6, err_msg=str(stage3))
print("ROUNDTRIP_OK")
"""


def test_stage3_checkpoint_roundtrip_across_prefetch_layers():
    """Save a stage-3 engine mid-training, reload into a fresh stage-3
    engine configured with a DIFFERENT prefetch_layers (and once into
    the naive up-front mode): the schedule is a trace-time choice, so
    restored state must be bit-identical and training must continue on
    the same trajectory as the uninterrupted run.

    Runs in a SUBPROCESS with the persistent compilation cache off:
    this is the one sequence that compiles new donated-buffer programs
    AFTER a checkpoint load, and in-process it reads whatever heap
    damage the suite's persistent-cache writes left behind — a
    pre-existing jaxlib landmine (glibc "corrupted size vs. prev_size"
    -> segfault/NaN, reproduced on the UNMODIFIED pre-PR tree with a
    plain stage-2 save/load/resume). A fresh process with no cache is
    deterministic every run (the memory-ledger OOM test precedent for
    subprocess isolation)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=8"])
    proc = subprocess.run(
        [sys.executable, "-c", _ROUNDTRIP_CHILD], env=env,
        capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ROUNDTRIP_OK" in proc.stdout, proc.stdout[-1000:]


# ----------------------------------------------------------------------
# sync-free hot loop guard (the async-dispatch acceptance, scheduled)
# ----------------------------------------------------------------------
class _SyncCounters:
    """Counts host<->device rendezvous a step loop must not use
    (`jax.device_get`, `jax.effects_barrier`) — the async-dispatch
    guard pattern, pointed at the scheduled stage-3 step."""

    def __init__(self, monkeypatch):
        self.device_get = 0
        self.effects_barrier = 0
        real_get, real_barrier = jax.device_get, jax.effects_barrier

        def counting_get(*a, **k):
            self.device_get += 1
            return real_get(*a, **k)

        def counting_barrier(*a, **k):
            self.effects_barrier += 1
            return real_barrier(*a, **k)

        monkeypatch.setattr(jax, "device_get", counting_get)
        monkeypatch.setattr(jax, "effects_barrier", counting_barrier)


def test_stage3_hot_loop_has_zero_host_syncs(monkeypatch):
    """With the gather scheduler ON, N train_batch steps after warmup
    perform ZERO jax.device_get / jax.effects_barrier calls: the whole
    gather/prefetch/release/reduce-scatter schedule is compiled into
    the step, never coordinated from the host."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    engine, _ = _build_gpt2_engine(
        3, **{"bf16": {"enabled": True},
              "async_dispatch": {"enabled": True}})
    assert engine.zero3_scheduler is not None
    batches = [engine.stage_batch(_gpt2_batch(i, stacked=True))
               for i in range(8)]
    for b in batches[:3]:
        engine.train_batch(batch=b)
    counters = _SyncCounters(monkeypatch)
    for b in batches[3:]:
        engine.train_batch(batch=b)
    assert counters.device_get == 0, \
        f"scheduled stage-3 hot path called jax.device_get " \
        f"{counters.device_get}x"
    assert counters.effects_barrier == 0
    assert np.isfinite(float(jax.device_get(engine.losses)))


# ----------------------------------------------------------------------
# memory-ledger window bound
# ----------------------------------------------------------------------
def _full_bytes(tree):
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("prefetch", [0, 1, 2])
def test_ledger_window_bytes_bound(prefetch):
    """zero3_gather in the ledger == gathered embeddings + exactly
    (prefetch_layers + 1) layers' full params — the live-bytes bound
    the tentpole claims. The expectation is computed INDEPENDENTLY
    from the raw param tree, not the scheduler's own bookkeeping."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    n_layer = 4
    engine, _ = _build_gpt2_engine(
        3, stage3={"prefetch_layers": prefetch}, n_layer=n_layer)
    _run(engine, 1)
    sched = engine.zero3_scheduler
    info = sched.stack_info["h"]
    window = min(prefetch, n_layer - 1) + 1
    assert info["window_layers"] == window

    (_, stacked), = engine.state.params["h"].items()
    per_layer = _full_bytes(stacked) // n_layer
    extras = sum(_full_bytes(engine.state.params[k])
                 for k in ("wte", "wpe", "ln_f"))
    cats = engine.monitor.ledger.totals()["hbm"]
    assert cats["zero3_gather"] == per_layer * window + extras
    # the bound: window <= (prefetch + 1) layers' worth
    assert per_layer * window <= per_layer * (prefetch + 1)


def test_ledger_naive_mode_records_whole_stack():
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    n_layer = 4
    engine, _ = _build_gpt2_engine(
        3, stage3={"release_after_use": False}, n_layer=n_layer)
    _run(engine, 1)
    info = engine.zero3_scheduler.stack_info["h"]
    assert info["window_layers"] == n_layer
    # and the ledger says so in bytes, against the raw param tree:
    # the embeddings and the final norm beside the WHOLE stack
    cats = engine.monitor.ledger.totals()["hbm"]
    assert cats["zero3_gather"] == sum(
        _full_bytes(engine.state.params[k])
        for k in ("h", "wte", "wpe", "ln_f"))


def test_oom_hints_name_prefetch_layers():
    from deepspeed_tpu.monitor.memory import oom_hints
    payload = {"hbm": {
        "categories": {"zero3_gather": 8 << 30, "params": 1 << 30},
        "ledger_bytes": 9 << 30,
        "measured_in_use_per_device": 10 << 30,
        "residual_bytes": 1 << 30}}
    hints = "\n".join(oom_hints(payload))
    assert "stage3.prefetch_layers" in hints


# ----------------------------------------------------------------------
# PipelineModule sequential chain
# ----------------------------------------------------------------------
def test_pipe_sequential_chain_stage3_parity():
    """The unrolled chained-loss path (pipe=1 PipelineModule): layer
    gathers fence on the activation prefetch_layers back, grads
    reduce-scatter through the gather's VJP — trajectory matches
    stage 2 to roundoff."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    import flax.linen as nn
    from deepspeed_tpu.runtime.pipe.module import (LayerSpec,
                                                   PipelineModule)

    class Mid(nn.Module):
        feats: int = 16

        @nn.compact
        def __call__(self, x):
            return nn.tanh(nn.Dense(self.feats)(x))

    mod = PipelineModule(
        layers=[LayerSpec(Mid) for _ in range(4)], num_stages=1,
        loss_fn=lambda x, y: jnp.mean((x - y) ** 2))
    params = mod.init_params(jax.random.PRNGKey(0),
                             np.zeros((2, 16), np.float32))

    def build(stage):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=mod,
            model_parameters=jax.tree_util.tree_map(np.copy, params),
            config=_engine_config(
                stage, gradient_accumulation_steps=2))
        return engine

    def run(engine):
        out = []
        for i in range(5):
            r = np.random.default_rng(i)
            x = r.standard_normal((16, 16)).astype(np.float32)
            out.append(float(jax.device_get(
                engine.train_batch(batch=(x, np.roll(x, 1, 1))))))
        return np.asarray(out)

    e3 = build(3)
    assert e3.zero3_scheduler is not None
    l3 = run(e3)
    l2 = run(build(2))
    np.testing.assert_allclose(l3, l2, rtol=0, atol=5e-6)
    info = e3.zero3_scheduler.stack_info["pipe_chain"]
    assert info["layers"] == 4 and info["window_layers"] == 2


# ----------------------------------------------------------------------
# ZeRO-Offload compressed-wire composition
# ----------------------------------------------------------------------
def test_stage3_composes_with_offload_compressed_wire():
    """stage 3 + cpu_offload + the PR-1 int8 wire: sharded compute
    params run the scheduled gathers while grads ride the compressed
    D2H wire into the host master update — the full composition the
    tentpole names. Loss stays finite and tracks the stage-2 offload
    engine; wire stats show real compression."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    cfg_over = {"zero_optimization": {
        "stage": 3, "cpu_offload": True,
        "offload_wire": {"grad_bits": 8, "param_bits": 8}}}

    def build(stage):
        model = GPT2ForCausalLM(tiny_gpt2_config(n_layer=2))
        params = model.init(jax.random.PRNGKey(0), _gpt2_batch(0))
        over = {k: dict(v, stage=stage) for k, v in cfg_over.items()}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 8,
                    "gradient_accumulation_steps": 1,
                    "steps_per_print": 10000,
                    "optimizer": {"type": "AdamW",
                                  "params": {"lr": 1e-3}},
                    **over})
        return engine

    e3 = build(3)
    assert e3.zero3_scheduler is not None
    l3 = _run(e3, 5)
    assert np.isfinite(l3).all()
    assert e3.wire_stats["d2h_bytes"] < 0.3 * \
        e3.wire_stats["d2h_bytes_native"], e3.wire_stats
    l2 = _run(build(2), 5)
    # int8 wire quantization is the same on both; trajectories track
    np.testing.assert_allclose(l3, l2, rtol=0, atol=1e-4)


# ----------------------------------------------------------------------
# config validation / ValueError contract
# ----------------------------------------------------------------------
def test_stage3_config_validation_raises_valueerror():
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
    with pytest.raises(ValueError, match="-3"):
        DeepSpeedZeroConfig({"zero_optimization": {
            "stage": 2, "stage3": {"prefetch_layers": -3}}})
    with pytest.raises(ValueError, match="int4"):
        DeepSpeedZeroConfig({"zero_optimization": {
            "stage": 3, "stage3": {"gather_dtype": "int4"}}})
    cfg = DeepSpeedZeroConfig({"zero_optimization": {
        "stage": 3, "stage3": {"prefetch_layers": 2,
                               "gather_dtype": "bf16"}}})
    assert cfg.stage3_prefetch_layers == 2
    assert cfg.stage3_enabled and cfg.stage3_release_after_use
    assert resolve_gather_dtype(cfg.stage3_gather_dtype) == jnp.bfloat16


def test_sharding_policy_stage_valueerror_names_value():
    """ZeroShardingPolicy rejects a bad stage with ValueError (visible
    under `python -O`, unlike the old bare assert) and the message
    carries the offending value."""
    mesh = _mesh()
    with pytest.raises(ValueError, match="7"):
        ZeroShardingPolicy(mesh, 7)
    with pytest.raises(ValueError, match="three"):
        ZeroShardingPolicy(mesh, "three")


def test_dropout_active_trace_stays_on_module_path():
    """With dropout > 0 and deterministic=False the scheduled path
    stands down (module path, identical dropout streams to the
    unscheduled engine — the ABCorrectnessChecker contract); eval
    traces (deterministic) still schedule."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    mesh = _mesh()
    model = GPT2ForCausalLM(tiny_gpt2_config(n_layer=2, dropout=0.1))
    batch = _gpt2_batch(1)
    params = model.init(jax.random.PRNGKey(0), batch)
    rngs = {"dropout": jax.random.PRNGKey(7)}

    l_plain = model.loss_fn(params, batch, rngs=rngs,
                            deterministic=False)
    model.bind_zero3_scheduler(Zero3GatherScheduler(mesh))
    assert not model._zero3_active(deterministic=False)
    assert model._zero3_active(deterministic=True)
    l_sched = model.loss_fn(params, batch, rngs=rngs,
                            deterministic=False)
    model.bind_zero3_scheduler(None)
    # identical dropout masks -> identical loss
    np.testing.assert_array_equal(np.asarray(l_plain),
                                  np.asarray(l_sched))


def test_gather_dtype_bf16_runs():
    """gather_dtype=bf16 on fp32 params: half the gather bytes, loss
    within bf16 tolerance of the fp32-gather run."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    e_ref, _ = _build_gpt2_engine(3, n_layer=2)
    e_bf, _ = _build_gpt2_engine(
        3, stage3={"gather_dtype": "bf16"}, n_layer=2)
    l_ref = _run(e_ref, 3)
    l_bf = _run(e_bf, 3)
    np.testing.assert_allclose(l_bf, l_ref, rtol=2e-2)
    info_ref = e_ref.zero3_scheduler.stack_info["h"]
    info_bf = e_bf.zero3_scheduler.stack_info["h"]
    assert info_bf["per_layer_bytes"] * 2 == \
        info_ref["per_layer_bytes"]
