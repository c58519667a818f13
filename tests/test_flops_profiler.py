"""FLOPS profiler tests (parity target: ref tests/unit/test_flops_profiler.py
asserts flops/params within tolerance of analytic values)."""

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.profiling.flops_profiler import (FlopsProfiler,
                                                    get_model_profile)
from deepspeed_tpu.profiling.flops_profiler.profiler import num_params


def test_cost_analysis_matmul():
    prof = FlopsProfiler()
    n = 256
    x = jnp.ones((n, n), jnp.float32)
    prof.start_profile()
    cost = prof.profile_jitted(lambda a: a @ a, x)
    prof.stop_profile()
    # 2*n^3 flops for a matmul
    assert abs(cost["flops"] - 2 * n ** 3) / (2 * n ** 3) < 0.05


def test_get_model_profile_flax():
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(64)(x)
            x = nn.relu(x)
            return nn.Dense(16)(x)

    flops, macs, params = get_model_profile(
        model=MLP(), args=(np.zeros((4, 32), np.float32),),
        print_profile=False, as_string=False)
    expect_params = 32 * 64 + 64 + 64 * 16 + 16
    assert params == expect_params
    # fwd flops >= the two matmuls
    assert flops >= 2 * 4 * 32 * 64 + 2 * 4 * 64 * 16


def test_engine_profile_step_runs(capsys):
    from deepspeed_tpu.models.gpt2 import tiny_gpt2_config, GPT2ForCausalLM
    cfg = tiny_gpt2_config(n_layer=2, dropout=0.0)
    model = GPT2ForCausalLM(cfg)
    ids = np.random.RandomState(0).randint(0, 256, (8, 64)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "flops_profiler": {"enabled": True, "profile_step": 2}})
    for _ in range(3):
        engine.train_batch(batch={"input_ids": ids[None]})
    # the profiler logged at step 2 without crashing; params counted
    assert num_params(engine.state.params) > 0
