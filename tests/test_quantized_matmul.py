"""Quantized-compute GEMM family (ISSUE 13 tentpole): the shared
per-block-scale layout, the dequant epilogues (weight-only + full
int8xint8, XLA fallback and interpret-mode Pallas kernel), the
straight-through backward, stochastic rounding, the GPT-2 weave
behind the `quantized_compute` config block (param-tree identity +
engine loss tracking), the boundary fusion that rides along, and the
inference dedupe (serving's quant module must BE the shared
primitive)."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the package re-exports the quantized_matmul FUNCTION, which shadows
# the submodule under `from ... import quantized_matmul`
qm = importlib.import_module(
    "deepspeed_tpu.ops.transformer.quantized_matmul")


def _rand(shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), dtype)


# ----------------------------------------------------------------------
# quantizers: np/jnp twins, scale layout, stochastic rounding
# ----------------------------------------------------------------------
def test_np_and_jnp_weight_quantizers_agree():
    w = np.random.default_rng(0).standard_normal((96, 40)) \
        .astype(np.float32)
    qn, sn = qm.quantize_kernel_int8_np(w, 32)
    qj, sj = qm.quantize_kernel_int8(jnp.asarray(w), 32,
                                     values_dtype=jnp.int8)
    # the jnp twin REALLY pads K to nb*block; the real rows must match
    # the numpy layout bit for bit, the pad rows must be zero
    assert np.array_equal(qn, np.asarray(qj)[:96])
    assert np.array_equal(sn, np.asarray(sj))
    assert qj.shape == (96, 40) and sn.shape == (3, 40)


def test_weight_quantizer_pads_k_and_zero_blocks_are_safe():
    w = np.zeros((50, 8), np.float32)
    w[:10, 0] = 3.0
    q, s = qm.quantize_kernel_int8(jnp.asarray(w), 32)
    assert q.shape == (64, 8)           # padded to 2 blocks
    assert np.asarray(q)[50:].max() == 0
    # all-zero blocks clamp their scale to 1 (no divide-by-zero, and
    # dequant reproduces the zeros exactly)
    deq = qm.dequantize_kernel(q, s, 32, k=50)
    assert np.allclose(np.asarray(deq), w, atol=3.0 / 127 / 2 + 1e-6)


def test_row_quantizer_layout_and_bound():
    x = _rand((5, 70))
    q, s = qm.quantize_rows_int8(x)
    assert q.shape == (5, 70) and s.shape == (5, 1)
    assert int(np.abs(np.asarray(q)).max()) <= 127
    deq = np.asarray(q).astype(np.float32) * np.asarray(s)
    step = np.asarray(s)  # one quantization step per row
    assert (np.abs(deq - np.asarray(x)) <= step / 2 + 1e-6).all()


def test_stochastic_rounding_is_unbiased_and_keyed():
    # row 0 pins the block scale at 0.3/127; the remaining rows sit at
    # 0.1 -> 42.33 quantization steps, a genuine straddle point
    w = np.full((256, 4), 0.1, np.float32)
    w[0] = 0.3
    w = jnp.asarray(w)
    q_n, s_n = qm.quantize_kernel_int8(w, 256)
    outs = []
    for seed in range(2):
        q_s, _ = qm.quantize_kernel_int8(
            w, 256, rng=jax.random.PRNGKey(seed))
        outs.append(np.asarray(q_s, np.float32))
    # different keys -> different rounding patterns, straddling the
    # true value; the mean over many draws recovers it (unbiased)
    assert not np.array_equal(outs[0], outs[1])
    scale = float(np.asarray(s_n)[0, 0])
    mean = outs[0][1:].mean() * scale
    assert abs(mean - 0.1) < 0.005
    assert set(np.unique(outs[0][1:])) <= {42.0, 43.0}


# ----------------------------------------------------------------------
# epilogues: weight-only (serving) + quantized compute (training)
# ----------------------------------------------------------------------
def test_weight_only_epilogue_tracks_dense():
    x = _rand((3, 7, 96))
    w = _rand((96, 32), seed=1)
    q, s = qm.quantize_kernel_int8_np(np.asarray(w), 32)
    y = qm.int8_matmul(x, jnp.asarray(q), jnp.asarray(s), 32,
                       jnp.float32)
    ref = np.asarray(x @ w)
    rel = np.abs(np.asarray(y) - ref).max() / np.abs(ref).max()
    assert rel < 0.05


def test_quantized_matmul_fallback_tracks_dense():
    x = _rand((16, 200))                    # K=200: padding to 2 blocks
    w = _rand((200, 48), seed=1)
    wq, sw = qm.quantize_kernel_int8(w, 128, values_dtype=jnp.float32)
    y = qm.quantized_matmul(x, wq, sw, block=128, impl="xla")
    ref = np.asarray(x @ w)
    rel = np.abs(np.asarray(y) - ref).max() / np.abs(ref).max()
    assert rel < 0.05


def test_pallas_kernel_matches_fallback_interpret():
    """The interpret-mode Pallas kernel (same kernel logic as real
    TPU) must agree with the XLA fallback to fp32 roundoff — integer
    products and block partial sums are exact in both."""
    x = _rand((40, 256))
    w = _rand((256, 192), seed=3)
    wq, sw = qm.quantize_kernel_int8(w, 128, values_dtype=jnp.int8)
    a = qm.quantized_matmul(x, wq.astype(jnp.float32), sw, block=128,
                            impl="xla")
    b = qm.quantized_matmul(x, wq, sw, block=128, impl="interpret",
                            block_m=128, block_n=128)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-4


def test_pallas_kernel_pads_m_and_n(monkeypatch):
    x = _rand((5, 128))                      # M=5 -> padded to bm
    w = _rand((128, 40), seed=2)             # N=40 -> padded to bn
    wq, sw = qm.quantize_kernel_int8(w, 128, values_dtype=jnp.int8)
    a = qm.quantized_matmul(x, wq.astype(jnp.float32), sw, block=128,
                            impl="xla")
    b = qm.quantized_matmul(x, wq, sw, block=128, impl="interpret",
                            block_m=128, block_n=128)
    assert a.shape == b.shape == (5, 40)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-4


def test_quantized_dense_ste_gradients():
    """Straight-through contract: dW is the exact full-precision
    x^T g; dx flows through the DEQUANTIZED effective weights."""
    x = _rand((6, 96))
    w = _rand((96, 32), seed=1)
    g = jnp.ones((6, 32))
    dx, dw = jax.grad(
        lambda x, w: qm.quantized_dense(x, w, block=128,
                                        impl="xla").sum(),
        argnums=(0, 1))(x, w)
    w_eff = qm.dequantize_kernel(
        *qm.quantize_kernel_int8(w, 128, values_dtype=jnp.float32),
        128, k=96)
    assert np.allclose(np.asarray(dx), np.asarray(g @ w_eff.T),
                       atol=1e-5)
    assert np.allclose(np.asarray(dw), np.asarray(x.T @ g), atol=1e-5)


def test_resolve_and_block_validation():
    assert qm.resolve_quantized_compute("off") is False
    assert qm.resolve_quantized_compute("on") is True
    assert qm.resolve_quantized_compute("auto") is False  # CPU CI
    with pytest.raises(ValueError):
        qm.resolve_quantized_compute("maybe")
    with pytest.raises(ValueError):
        qm.quantized_dense(_rand((4, 128)), _rand((128, 8)), block=0)
    with pytest.raises(ValueError):
        # Pallas path requires 128-multiple blocks (int8 lane tiling)
        qm.quantized_dense(_rand((4, 128)), _rand((128, 8)), block=64,
                           impl="interpret")
    # ...but the XLA fallback takes finer blocks
    y = qm.quantized_dense(_rand((4, 128)), _rand((128, 8)), block=64,
                           impl="xla")
    assert y.shape == (4, 8)


def test_bf16_fallback_is_bit_identical_without_sr():
    x = _rand((8, 64), jnp.bfloat16)
    w = _rand((64, 32), jnp.bfloat16, seed=1)
    y = qm.bf16_fallback_matmul(x, w, out_dtype=jnp.bfloat16)
    ref = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())))
    assert np.array_equal(np.asarray(y, np.float32),
                          np.asarray(ref, np.float32))
    # SR + rng: still close, not identical
    ysr = qm.bf16_fallback_matmul(
        _rand((8, 64)), _rand((64, 32), seed=1),
        out_dtype=jnp.bfloat16, stochastic_rounding=True,
        rng=jax.random.PRNGKey(0))
    assert not np.array_equal(np.asarray(ysr, np.float32),
                              np.asarray(ref, np.float32))
    assert np.abs(np.asarray(ysr, np.float32) -
                  np.asarray(ref, np.float32)).max() < 0.5


# ----------------------------------------------------------------------
# the serving dedupe: the int8 load and a served block's dense
# application ARE the shared primitive
# ----------------------------------------------------------------------
def test_inference_quant_is_the_shared_primitive():
    from deepspeed_tpu.inference import engine
    from deepspeed_tpu.models import gpt2
    assert gpt2.int8_matmul is qm.int8_matmul
    assert gpt2.KERNEL_SCALE is engine.KERNEL_SCALE is qm.KERNEL_SCALE
    w = np.random.RandomState(0).randn(3, 40, 8).astype(np.float32)
    tree = {"h": {"c_fc": {"kernel": w, "bias": np.zeros((3, 8))},
                  "ln_1": {"scale": np.ones((3, 40))}}, "wte": w[0]}
    got = engine.quantize_param_tree(tree, 16, ("c_fc",))
    q, s = qm.quantize_kernel_int8_np(w, 16)
    leaf = got["h"]["c_fc"]
    assert np.array_equal(leaf["kernel"], q) and leaf["kernel"].dtype == np.int8
    assert np.array_equal(leaf[qm.KERNEL_SCALE], s)
    assert got["wte"] is tree["wte"] and got["h"]["ln_1"] == tree["h"]["ln_1"]
    assert engine.quantize_param_tree(tree, 16, ())["h"]["c_fc"] == \
        tree["h"]["c_fc"]


# ----------------------------------------------------------------------
# the GPT-2 weave: config block -> engine hook -> projections
# ----------------------------------------------------------------------
def _tiny(**kw):
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, \
        tiny_gpt2_config
    cfg = tiny_gpt2_config(n_positions=64, **kw)
    return GPT2ForCausalLM(cfg)


def test_param_tree_identical_quantized_or_not():
    ids = np.zeros((2, 64), np.int32)
    trees = []
    for kw in ({}, {"quantized_compute": "on"},
               {"quantized_compute": "on", "fused_ops": "on"}):
        m = _tiny(**kw)
        p = m.init(jax.random.PRNGKey(0), {"input_ids": ids})
        trees.append(str(jax.tree_util.tree_map(
            lambda l: (l.shape, str(l.dtype)), p)))
    assert trees[0] == trees[1] == trees[2]


def test_quantized_loss_tracks_unquantized():
    ids = np.random.default_rng(0).integers(
        0, 256, (2, 64)).astype(np.int32)
    batch = {"input_ids": ids}
    m0, m1 = _tiny(), _tiny(quantized_compute="on")
    p = m0.init(jax.random.PRNGKey(0), {"input_ids": ids})
    l0 = float(m0.loss_fn(p, batch, deterministic=True))
    l1 = float(m1.loss_fn(p, batch, deterministic=True))
    assert l0 != l1                      # it actually quantized
    assert abs(l0 - l1) / abs(l0) < 0.01


def test_configure_hook_and_mode_validation():
    m = _tiny()
    with pytest.raises(ValueError):
        m.configure_quantized_compute("sideways")
    m.configure_quantized_compute("on", block=128,
                                  stochastic_rounding=True)
    assert m.config.quantized_compute == "on"
    assert m.config.quant_block == 128
    assert m.config.quant_stochastic_rounding is True


def test_engine_wires_quantized_compute_and_emits_event(tmp_path):
    """The `quantized_compute` config block reaches the model through
    the engine (configure hook), the per-step "quant" rng stream
    feeds stochastic rounding, and one `quantized_matmul` event lands
    in the JSONL sink."""
    import json
    import deepspeed_tpu
    ids = np.random.default_rng(0).integers(
        0, 256, (1, 8, 64)).astype(np.int32)
    model = _tiny()
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": ids[0]})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 8,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 1000,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "quantized_compute": {"enabled": True, "mode": "on",
                                  "block": 128,
                                  "stochastic_rounding": True},
            "monitor": {"enabled": True, "sinks": ["jsonl"],
                        "output_path": str(tmp_path)},
        })
    assert model.config.quantized_compute == "on"
    assert model.config.quant_stochastic_rounding is True
    loss = engine.train_batch(batch={"input_ids": ids})
    assert np.isfinite(float(jax.device_get(loss)))
    engine.monitor.close()
    events = [json.loads(l) for l in
              open(tmp_path / "events.jsonl")]
    qevents = [e for e in events if e["kind"] == "quantized_matmul"]
    assert len(qevents) == 1
    ev = qevents[0]
    assert ev["applied"] is True and ev["active"] is True
    assert ev["mode"] == "on" and ev["block"] == 128
    assert ev["stochastic_rounding"] is True


def test_engine_loss_trajectory_tracks_unquantized():
    """Ten steps of the same tiny GPT-2 on the same data with the
    `quantized_compute` block on and off: the int8 forward perturbs
    the trajectory and must not leave it (every step within 0.2 of
    the unquantized loss, and really another number)."""
    import deepspeed_tpu
    ids = np.random.default_rng(1).integers(
        0, 256, (10, 1, 8, 64)).astype(np.int32)

    def run(quant):
        model = _tiny()
        params = model.init(jax.random.PRNGKey(0),
                            {"input_ids": ids[0, 0]})
        config = {"train_micro_batch_size_per_gpu": 8,
                  "gradient_accumulation_steps": 1,
                  "steps_per_print": 1000,
                  "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
        if quant:
            config["quantized_compute"] = {
                "enabled": True, "mode": "on",
                "block": qm.DEFAULT_QUANT_BLOCK}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=config)
        return [float(jax.device_get(
            engine.train_batch(batch={"input_ids": ids[i]})))
            for i in range(10)]

    base, quant = run(False), run(True)
    assert base != quant
    assert max(abs(a - b) for a, b in zip(base, quant)) <= 0.2


def test_engine_warns_when_model_lacks_hook(caplog):
    import deepspeed_tpu

    def loss_fn(params, batch, rngs=None, deterministic=False):
        return jnp.mean((batch["x"] @ params["w"]) ** 2)

    class Plain:
        pass

    model = Plain()
    model.loss_fn = loss_fn
    params = {"w": _rand((8, 8))}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "quantized_compute": {"enabled": True, "mode": "on"},
        })
    # no hook -> warned, engine still works
    assert engine is not None


def test_config_block_validation():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)
    base = {"train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1}
    for bad in ({"quantized_compute": {"mode": "nope"}},
                {"quantized_compute": {"block": 0}},
                {"quantized_compute": {"block": True}},
                {"quantized_compute": "yes"},
                {"autotune": {"table_path": 7}},
                {"autotune": []}):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig({**base, **bad}, world_size=1)
    cfg = DeepSpeedConfig(
        {**base,
         "quantized_compute": {"enabled": True, "mode": "on",
                               "block": 256,
                               "stochastic_rounding": True},
         "autotune": {"enabled": False, "table_path": "/tmp/t.json"}},
        world_size=1)
    assert cfg.quantized_compute == {
        "enabled": True, "mode": "on", "block": 256,
        "stochastic_rounding": True}
    assert cfg.autotune == {"enabled": False,
                            "table_path": "/tmp/t.json"}


def test_sr_bf16_fallback_is_wired_when_quant_resolves_off():
    """quantized_compute 'auto' resolves OFF on CPU; with
    stochastic_rounding the documented bf16 fallback must engage:
    bit-identical to the plain model without a "quant" rng,
    stochastically perturbed (but close) with one."""
    ids = np.random.default_rng(4).integers(
        0, 256, (2, 64)).astype(np.int32)
    batch = {"input_ids": ids}
    m_plain = _tiny(dtype=jnp.bfloat16)
    m_sr = _tiny(dtype=jnp.bfloat16, quantized_compute="auto",
                 quant_stochastic_rounding=True)
    p = m_plain.init(jax.random.PRNGKey(0), {"input_ids": ids})
    l_plain = float(m_plain.loss_fn(p, batch, deterministic=True))
    l_no_rng = float(m_sr.loss_fn(p, batch, deterministic=True))
    assert l_plain == l_no_rng      # backward compatible without rng
    l_rng = float(m_sr.loss_fn(
        p, batch, rngs={"quant": jax.random.PRNGKey(1)},
        deterministic=True))
    assert l_rng != l_plain         # SR casts actually engaged
    assert abs(l_rng - l_plain) / abs(l_plain) < 0.01


# ----------------------------------------------------------------------
# boundary fusion (ISSUE 13(c)) — rides the fused path
# ----------------------------------------------------------------------
def test_boundary_fused_loss_bit_exact_and_grads_roundoff():
    ids = np.random.default_rng(1).integers(
        0, 256, (2, 64)).astype(np.int32)
    batch = {"input_ids": ids}
    m0, m1 = _tiny(), _tiny(fused_ops="on")
    p = m0.init(jax.random.PRNGKey(0), {"input_ids": ids})
    l0 = float(m0.loss_fn(p, batch, deterministic=True))
    l1 = float(m1.loss_fn(p, batch, deterministic=True))
    assert l0 == l1                      # fp32 forward is bit-exact
    g0 = jax.grad(lambda p: m0.loss_fn(p, batch,
                                       deterministic=True))(p)
    g1 = jax.grad(lambda p: m1.loss_fn(p, batch,
                                       deterministic=True))(p)
    gmax = max(float(jnp.abs(l).max())
               for l in jax.tree_util.tree_leaves(g0))
    gd = max(float(jnp.abs(a - b).max())
             for a, b in zip(jax.tree_util.tree_leaves(g1),
                             jax.tree_util.tree_leaves(g0)))
    assert gd / gmax < 1e-5


def test_boundary_fusion_mirrors_on_zero3_scheduled_path():
    """The stage-3 scheduled loss must run the same boundary-fused op
    sequence as the module path: loss parity at the fused-path
    tolerance with the scheduler bound."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, \
        tiny_gpt2_config
    ids = np.random.default_rng(2).integers(
        0, 256, (1, 8, 64)).astype(np.int32)
    cfg = tiny_gpt2_config(n_positions=64, fused_ops="on")
    model = GPT2ForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": ids[0]})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 8,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 1000,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {
                "stage": 3, "stage3": {"prefetch_layers": 1}},
        })
    assert engine.zero3_scheduler is not None
    losses = [float(jax.device_get(
        engine.train_batch(batch={"input_ids": ids})))
        for _ in range(3)]
    assert all(np.isfinite(l) for l in losses)


def test_boundary_block_requires_fused_path():
    from deepspeed_tpu.models.gpt2 import GPT2Block, tiny_gpt2_config
    cfg = tiny_gpt2_config(n_positions=64)   # fused auto -> off on CPU
    blk = GPT2Block(cfg)
    x = _rand((2, 8, 64))
    with pytest.raises(ValueError):
        blk.init(jax.random.PRNGKey(0), x, True, None, True)


def test_pld_keeps_plain_carry_under_fused():
    """layer_keep_prob forces the non-boundary carry (PLD gates on
    completed block outputs) — and still runs with fused_ops on."""
    ids = np.random.default_rng(3).integers(
        0, 256, (2, 64)).astype(np.int32)
    m = _tiny(fused_ops="on")
    p = m.init(jax.random.PRNGKey(0), {"input_ids": ids})
    l = float(m.loss_fn(p, {"input_ids": ids}, deterministic=True,
                        layer_keep_prob=jnp.float32(1.0)))
    l_ref = float(_tiny().loss_fn(p, {"input_ids": ids},
                                  deterministic=True))
    assert abs(l - l_ref) < 1e-5
