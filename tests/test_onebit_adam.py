"""1-bit Adam tests (parity target: ref `deepspeed/runtime/fp16/
onebit_adam.py:104-372`): warmup phase must be exact Adam, the
freeze_step transition must switch the engine onto the compressed
shard_map program whose only cross-worker payload is bit-packed signs,
and the compressed phase must still converge.

Runs on the 8-device virtual CPU mesh from conftest."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from simple_model import SimpleModel
from deepspeed_tpu.runtime.fp16.onebit_adam import (
    pack_signs, unpack_signs, compress, compressed_allreduce)

DIM = 16
BS = 16


def onebit_config(freeze_step, lr=1e-2, **over):
    cfg = {
        "train_batch_size": BS,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 1000,
        "optimizer": {"type": "OneBitAdam",
                      "params": {"lr": lr, "freeze_step": freeze_step}},
    }
    cfg.update(over)
    return cfg


def adam_config(lr=1e-2):
    return {
        "train_batch_size": BS,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 1000,
        "optimizer": {"type": "Adam", "params": {"lr": lr}},
    }


def make_stacked_batch(seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(BS, DIM).astype(np.float32)
    w = np.linspace(-1, 1, DIM * DIM).reshape(DIM, DIM).astype(np.float32)
    # leading gas=1 dim for the fused train_batch path
    return {"x": x[None], "y": (x @ w)[None]}


def run_train(config, steps, seed=0):
    model = SimpleModel(hidden_dim=DIM, seed=seed)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.params, config=config)
    losses = []
    for i in range(steps):
        loss = engine.train_batch(batch=make_stacked_batch(i % 4))
        losses.append(float(jax.device_get(loss)))
    return engine, losses


# ----------------------------------------------------------------------
# compression primitives
# ----------------------------------------------------------------------
def test_pack_unpack_roundtrip():
    x = jnp.asarray(np.random.RandomState(0).randn(37), jnp.float32)
    signs = unpack_signs(pack_signs(x), 37)
    np.testing.assert_array_equal(np.asarray(signs),
                                  np.where(np.asarray(x) >= 0, 1.0, -1.0))


def test_error_feedback_invariant():
    """compress() must satisfy scale*signs + new_error == x + error —
    nothing is lost, only deferred (ref worker_error semantics)."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(64), jnp.float32)
    err = jnp.asarray(rng.randn(64) * 0.1, jnp.float32)
    scale, packed, new_err = compress(x, err)
    recon = unpack_signs(packed, 64) * scale + new_err
    np.testing.assert_allclose(np.asarray(recon), np.asarray(x + err),
                               rtol=1e-5, atol=1e-6)


def test_compressed_allreduce_approximates_mean(mesh8):
    """Across 8 shards with distinct inputs, the compressed result must
    approximate the true mean (one sign+scale quantization away)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = 128
    rng = np.random.RandomState(2)
    data = jnp.asarray(rng.randn(8, n), jnp.float32)

    def per_shard(x):
        x = x[0]
        out, werr, serr = compressed_allreduce(
            x, jnp.zeros_like(x), jnp.zeros_like(x), "data")
        return out[None]

    out = shard_map(per_shard, mesh=mesh8,
                    in_specs=P("data"), out_specs=P("data"),
                    check_vma=False)(data)
    out = np.asarray(out)
    # every shard holds the same server-compressed average
    for i in range(1, 8):
        np.testing.assert_allclose(out[i], out[0], rtol=1e-6)
    true_mean = np.asarray(data).mean(axis=0)
    # sign*scale quantization: direction must correlate strongly
    cos = np.dot(out[0], true_mean) / (
        np.linalg.norm(out[0]) * np.linalg.norm(true_mean))
    assert cos > 0.5, cos


# ----------------------------------------------------------------------
# engine integration
# ----------------------------------------------------------------------
def test_warmup_matches_adam():
    """Before freeze_step, 1-bit Adam IS Adam (ref onebit_adam.py:320:
    warmup runs the uncompressed update)."""
    _, losses_1bit = run_train(onebit_config(freeze_step=1000), steps=8)
    _, losses_adam = run_train(adam_config(), steps=8)
    np.testing.assert_allclose(losses_1bit, losses_adam, rtol=1e-5)


def test_compressed_phase_activates_and_converges():
    engine, losses = run_train(onebit_config(freeze_step=3), steps=40)
    assert engine._use_onebit_shardmap
    assert engine._onebit_compressed_active
    assert np.isfinite(losses).all()
    # compressed phase continues to make progress
    assert losses[-1] < losses[3] * 0.5, losses


def test_compressed_converges_comparably_to_adam():
    """End-to-end convergence parity claim (ref README.md:39: same
    convergence as Adam)."""
    _, losses_1bit = run_train(onebit_config(freeze_step=5), steps=50)
    _, losses_adam = run_train(adam_config(), steps=50)
    assert losses_1bit[-1] < max(losses_adam[-1] * 3.0, 1e-3), \
        (losses_1bit[-1], losses_adam[-1])


def test_compressed_wire_is_bitpacked():
    """The compressed-phase program's gradient communication must be
    uint8 sign payloads — no dense fp32 grad allreduce may remain
    (the point of ref onebit_adam.py:372 disabling backward allreduce)."""
    model = SimpleModel(hidden_dim=DIM)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.params,
        config=onebit_config(freeze_step=1))
    batch = jax.tree_util.tree_map(
        lambda x: jnp.asarray(x), make_stacked_batch(0))
    lowered = engine._onebit_compressed_jit.lower(
        engine.state, batch, jax.random.PRNGKey(0),
        jnp.float32(1e-2), jnp.float32(1.0))
    text = lowered.as_text()
    # the momentum collective: bit-packed uint8 all_gather
    assert "ui8" in text and "all_gather" in text
    # any surviving all_reduce must be scalar (loss pmean / norm vote);
    # a non-scalar one would be a dense gradient reduction
    import re
    operand_types = re.findall(
        r'"stablehlo\.all_reduce".*?\}\) : \(tensor<([^>]*)>', text, re.S)
    assert operand_types, "no all_reduce found (expected scalar votes)"
    for t in operand_types:
        assert not re.match(r"^\d", t), \
            f"dense grad allreduce survived: tensor<{t}>"


def test_worker_error_is_per_worker_state():
    """worker_error must carry a leading [dp] dim sharded over data —
    each worker owns its own error-feedback slice (ref allocates it per
    rank, onebit_adam.py:305). After compressed steps the slices must
    actually diverge (they see different local momenta)."""
    engine, _ = run_train(onebit_config(freeze_step=2), steps=10)
    werr = engine.state.opt_state.worker_error
    for leaf, p in zip(jax.tree_util.tree_leaves(werr),
                       jax.tree_util.tree_leaves(engine.state.params)):
        assert leaf.shape == (8,) + p.shape, (leaf.shape, p.shape)
        host = np.asarray(jax.device_get(leaf))
        assert not np.allclose(host[0], host[1]), \
            "worker error slices identical: per-worker feedback collapsed"


def test_resume_without_optimizer_states_rewarms(tmp_path):
    """Reloading past freeze_step with load_optimizer_states=False must
    re-enter warmup (fresh count=0, all-zero frozen variance would
    otherwise explode)."""
    engine, _ = run_train(onebit_config(freeze_step=3), steps=6)
    assert engine._onebit_compressed_active
    engine.save_checkpoint(str(tmp_path))
    engine.wait_for_checkpoint()

    model = SimpleModel(hidden_dim=DIM)
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.params,
        config=onebit_config(freeze_step=3))
    engine2.load_checkpoint(str(tmp_path), load_optimizer_states=False)
    loss = engine2.train_batch(batch=make_stacked_batch(0))
    assert not engine2._onebit_compressed_active
    assert np.isfinite(float(jax.device_get(loss)))

    # with optimizer states the phase resumes compressed
    engine3, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=DIM).__class__(hidden_dim=DIM),
        model_parameters=SimpleModel(hidden_dim=DIM).params,
        config=onebit_config(freeze_step=3))
    engine3.load_checkpoint(str(tmp_path), load_optimizer_states=True)
    engine3.train_batch(batch=make_stacked_batch(0))
    assert engine3._onebit_compressed_active


def test_onebit_respects_lr_scheduler():
    """OnebitAdamState exposes an injectable learning_rate hyperparam
    so LR schedules apply (the reference reads group['lr'] each step)."""
    cfg = onebit_config(freeze_step=100)
    cfg["scheduler"] = {"type": "WarmupLR",
                        "params": {"warmup_min_lr": 0.0,
                                   "warmup_max_lr": 1e-2,
                                   "warmup_num_steps": 10}}
    model = SimpleModel(hidden_dim=DIM)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.params, config=cfg)
    p0 = jax.device_get(engine.state.params)
    engine.train_batch(batch=make_stacked_batch(0))
    p1 = jax.device_get(engine.state.params)
    # first warmup step: lr ~ 0 → params barely move
    delta = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                for a, b in zip(jax.tree_util.tree_leaves(p0),
                                jax.tree_util.tree_leaves(p1)))
    assert delta < 1e-4, f"scheduler lr not applied (delta={delta})"


def test_onebit_fallback_single_worker():
    """With a trivial mesh gate miss (zero stage 2), the engine must
    fall back to the dynamic single-worker form and still train."""
    cfg = onebit_config(freeze_step=3,
                        zero_optimization={"stage": 2})
    engine, losses = run_train(cfg, steps=10)
    assert not engine._use_onebit_shardmap
    assert np.isfinite(losses).all()
