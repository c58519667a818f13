#!/usr/bin/env python
"""BERT pretraining (MLM + NSP) with the fused DeepSpeedTransformerLayer
— the bing_bert example shape from DeepSpeedExamples, TPU-native.

Run:
    python examples/bert_pretrain.py --deepspeed \
        --deepspeed_config examples/ds_config_bert.json
"""

import argparse
import os
import sys

import jax
import numpy as np

# runnable from a source checkout without installation
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.models.bert import BertForPreTrainingLM, bert_config
from deepspeed_tpu.utils.compile_cache import enable_compile_cache


def get_args():
    parser = argparse.ArgumentParser(description="BERT pretraining")
    parser.add_argument("--model", default="bert-large",
                        help="bert-tiny | bert-base | bert-large")
    parser.add_argument("--seq-len", type=int, default=128)
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


def synthetic_batches(vocab, micro_bs, gas, seq, seed, num_batches=0):
    rng = np.random.default_rng(seed)

    def make():
        ids = rng.integers(0, vocab, (gas, micro_bs, seq)).astype(np.int32)
        labels = np.where(rng.random((gas, micro_bs, seq)) < 0.15,
                          ids, -100).astype(np.int32)
        return {"input_ids": ids, "masked_lm_labels": labels,
                "next_sentence_label": rng.integers(
                    0, 2, (gas, micro_bs)).astype(np.int32)}

    fixed = [make() for _ in range(num_batches)] if num_batches else None
    i = 0
    while True:
        if fixed is not None:
            yield fixed[i % len(fixed)]
            i += 1
        else:
            yield make()


def main():
    enable_compile_cache()
    args = get_args()
    cfg = bert_config(args.model, max_position_embeddings=args.seq_len,
                      hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0, bf16=True)
    model = BertForPreTrainingLM(cfg)
    example = {"input_ids": np.zeros((1, args.seq_len), np.int32)}
    params = model.init(jax.random.PRNGKey(args.seed), example)

    engine, _, _, _ = deepspeed_tpu.initialize(
        args=args, model=model, model_parameters=params)

    data = synthetic_batches(cfg.vocab_size,
                             engine.train_micro_batch_size_per_gpu(),
                             engine.gradient_accumulation_steps(),
                             args.seq_len, args.seed, args.num_batches)
    losses = []
    for step in range(args.steps):
        loss = engine.train_batch(batch=next(data))
        losses.append(loss)    # fetched after the loop — no per-step sync
        if step % engine.steps_per_print() == 0:
            deepspeed_tpu.log_dist(
                f"step {step}: loss {float(jax.device_get(loss)):.4f}",
                ranks=[0])
    traj = [round(float(jax.device_get(l)), 6) for l in losses]
    print("LM loss trajectory:", " ".join(f"{x:.6f}" for x in traj),
          flush=True)
    if args.save_dir:
        engine.save_checkpoint(args.save_dir)
        # commit barrier: the save is async by default
        engine.wait_for_checkpoint()


if __name__ == "__main__":
    main()
