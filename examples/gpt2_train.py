#!/usr/bin/env python
"""Minimal GPT-2 pretraining with deepspeed_tpu — the Megatron-GPT2
example shape from DeepSpeedExamples, TPU-native.

Run (single host):
    python examples/gpt2_train.py --deepspeed \
        --deepspeed_config examples/ds_config_gpt2.json

Multi-host (pod): launch with `bin/dstpu --hostfile ... examples/gpt2_train.py ...`
and the engine picks up jax.distributed from the launcher env.
"""

import argparse
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

# runnable from a source checkout without installation
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config
from deepspeed_tpu.utils.compile_cache import enable_compile_cache


def get_args():
    parser = argparse.ArgumentParser(description="GPT-2 pretraining")
    parser.add_argument("--model", default="gpt2-125m",
                        help="gpt2-tiny .. gpt2-13b")
    parser.add_argument("--seq-len", type=int, default=1024)
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--save-dir", default=None,
                        help="checkpoint dir (omit to skip saving)")
    parser.add_argument("--num-batches", type=int, default=0,
                        help="cycle a FIXED set of N synthetic batches "
                             "(learnable; the model harness uses this) "
                             "instead of an endless random stream")
    parser = deepspeed_tpu.add_config_arguments(parser)
    return parser.parse_args()


def synthetic_batches(vocab, rows, gas, seq, seed, num_batches=0):
    rng = np.random.default_rng(seed)
    fixed = [{"input_ids": rng.integers(
        0, vocab, (gas, rows, seq)).astype(np.int32)}
        for _ in range(num_batches)] if num_batches else None
    i = 0
    while True:
        if fixed is not None:
            yield fixed[i % len(fixed)]
            i += 1
        else:
            yield {"input_ids": rng.integers(
                0, vocab, (gas, rows, seq)).astype(np.int32)}


def build_model(name, seq_len, seed):
    """(model, params) the way this example trains them.

    Selective remat (save matmul outputs) is the throughput sweet spot
    up to ~1B params; beyond that the saved activations exceed HBM and
    full remat (policy None) is required. bf16 param STORAGE likewise
    becomes mandatory at flagship scale (see ds_config_gpt2_1.5b.json);
    the compute dtype is bf16 at every size."""
    big = name in ("gpt2-1.5b", "gpt2-2.7b", "gpt2-6.7b", "gpt2-13b")
    cfg = gpt2_config(name, n_positions=seq_len, dropout=0.0,
                      remat=True,
                      remat_policy=(None if big else
                                    "dots_with_no_batch_dims_saveable"),
                      **({"param_dtype": jnp.bfloat16} if big else {}))
    model = GPT2ForCausalLM(cfg)
    example = {"input_ids": np.zeros((1, seq_len), np.int32)}
    # jitted: one program that returns only the parameters, instead of
    # an op-by-op forward pass whose activations sit beside them
    params = jax.jit(lambda rng: model.init(rng, example))(
        jax.random.PRNGKey(seed))
    return model, params


def main():
    enable_compile_cache()
    args = get_args()
    model, params = build_model(args.model, args.seq_len, args.seed)
    cfg = model.config

    engine, _, _, _ = deepspeed_tpu.initialize(
        args=args, model=model, model_parameters=params)
    # the engine holds its own copy; at 1.5B a second 3.1 GB tree
    # decides whether the config's micro-batch fits a 16 GB chip
    del params

    # a step's batch is [gas, rows, seq] with the rows divided over the
    # data axis: micro-batch per chip times the chips on that axis
    rows = engine.train_micro_batch_size_per_gpu() * engine.dp_world_size
    gas = engine.gradient_accumulation_steps()
    data = synthetic_batches(cfg.vocab_size, rows, gas, args.seq_len,
                             args.seed, args.num_batches)
    losses = []
    for step in range(args.steps):
        loss = engine.train_batch(batch=next(data))
        losses.append(loss)    # fetched after the loop — no per-step sync
        if step % engine.steps_per_print() == 0:
            deepspeed_tpu.log_dist(
                f"step {step}: loss {float(jax.device_get(loss)):.4f}",
                ranks=[0])
    # full trajectory in one greppable line (the model-level regression
    # harness parses this; ref run_func_test.py greps "LM loss:")
    traj = [round(float(jax.device_get(l)), 6) for l in losses]
    print("LM loss trajectory:", " ".join(f"{x:.6f}" for x in traj),
          flush=True)
    if args.save_dir:
        engine.save_checkpoint(args.save_dir)
        # commit barrier: the save is async by default
        engine.wait_for_checkpoint()


if __name__ == "__main__":
    main()
