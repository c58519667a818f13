#!/usr/bin/env python
"""3D-parallel pipeline training: PipelineModule on a pipe x data x
model mesh — the shape of the reference's Megatron+pipeline examples
(`PipeModelDataParallelTopology`, ref topology.py:246-249), TPU-native.

The compiled 1F1B executor clock-aligns the TrainSchedule instruction
streams into one SPMD program; stage parameters live in flat [S, F]
buffers sharded over (pipe, model), so parameter/optimizer memory
divides by pipe*model (*data for ZeRO-sharded state).

Run on the 8-device virtual CPU mesh:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/pipeline_3d_train.py
On a real slice, drop the env vars and size the mesh to the chips.
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.runtime.pipe.module import (LayerSpec,  # noqa: E402
                                               PipelineModule)
from deepspeed_tpu.utils.compile_cache import \
    enable_compile_cache  # noqa: E402


def get_args():
    p = argparse.ArgumentParser(description="3D pipeline training")
    p.add_argument("--pipe", type=int, default=2)
    p.add_argument("--model-par", type=int, default=2)
    p.add_argument("--data", type=int, default=-1)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--gas", type=int, default=4,
                   help="microbatches per step (>= pipe stages for "
                        "pipeline overlap; gas=1 with pipe>1 is refused)")
    p = deepspeed_tpu.add_config_arguments(p)
    return p.parse_args()


def main():
    enable_compile_cache()
    import jax
    import flax.linen as nn
    import jax.numpy as jnp

    args = get_args()
    h = args.hidden

    def mse(pred, labels):
        return jnp.mean((pred.astype(jnp.float32) -
                         labels.astype(jnp.float32)) ** 2)

    # heterogeneous on purpose: widths differ per stage, one paramless
    # callable in the chain — the case the 1F1B interpreter exists for
    module = PipelineModule(
        layers=[LayerSpec(nn.Dense, h),
                jnp.tanh,
                LayerSpec(nn.Dense, 2 * h),
                LayerSpec(nn.Dense, h // 2)],
        num_stages=args.pipe,
        loss_fn=mse,
        partition_method="parameters")

    rng = np.random.RandomState(0)
    example = jnp.asarray(rng.randn(4, h), jnp.float32)
    params = module.init_params(jax.random.PRNGKey(0), example)

    config = {
        "train_micro_batch_size_per_gpu": 8,
        "gradient_accumulation_steps": args.gas,
        "steps_per_print": 5,
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "mesh": {"pipe": args.pipe, "data": args.data,
                 "model": args.model_par},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        args=args, model=module, model_parameters=params, config=config)

    w = np.linspace(-1, 1, h * (h // 2)).reshape(h, h // 2)
    bs = 8 * args.gas
    for step in range(args.steps):
        x = rng.randn(bs, h).astype(np.float32)
        loss = engine.train_batch(batch={"x": x, "y": (x @ w)})
        if step % 5 == 0:
            print(f"step {step}: loss {float(jax.device_get(loss)):.4f}",
                  flush=True)

    # show the memory partitioning the mesh bought
    for dt, buf in engine.state.params["flat"].items():
        shard = buf.addressable_shards[0].data.shape
        print(f"flat[{dt}] global {tuple(buf.shape)} -> per-device "
              f"{tuple(shard)} (pipe x model partitioned)", flush=True)


if __name__ == "__main__":
    main()
