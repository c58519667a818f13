#!/usr/bin/env python3
"""The largest micro-batch of a training cell that the chip's compiler
accepts with room to spare: compiles the cell's train step (nothing
runs) at each candidate and prints the compiler's memory report. The
number found is then written into the traffic file by hand, with the
report.

    python3 benchmark/find_micro_batch.py --workload <cell> \
        --candidates 16,24,32,48,64 [--spare-gb 0.5]
"""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM
    from benchmark import harness, weights
    from benchmark.kinds.train import model_config
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--candidates", required=True)
    ap.add_argument("--spare-gb", type=float, default=0.5)
    args = ap.parse_args()
    cell = harness.load_cell(harness.load_benchmark(), args.workload)
    info = harness.require_tpu(cell["chips"])
    harness.enable_compile_cache()
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    sizes, mix = cell["sizes"], cell["mix"]
    seq = mix["seq_len"]
    cfg = model_config(sizes, seq)
    best = None
    for micro in (int(x) for x in args.candidates.split(",")):
        model = GPT2ForCausalLM(cfg)
        template = jax.eval_shape(
            lambda k: model.init(k, {"input_ids": np.zeros((1, seq),
                                                           np.int32)}),
            jax.random.PRNGKey(0))
        params = weights.to_program_tree(
            weights.make_weights(sizes, 0, cfg.param_dtype), template)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=harness.merged(
                mix["ds_config"], {"train_micro_batch_size_per_gpu": micro}))
        del params
        rows = micro * engine.dp_world_size
        batch = {"input_ids": np.zeros((1, rows, seq), np.int32)}
        row = {"micro_batch": micro, "bytes_limit": limit}
        try:
            m = engine.lower_train_step(batch).compile().memory_analysis()
            row.update(
                argument_bytes=m.argument_size_in_bytes,
                output_bytes=m.output_size_in_bytes,
                alias_bytes=m.alias_size_in_bytes,
                temp_bytes=m.temp_size_in_bytes)
            row["total_bytes"] = (m.argument_size_in_bytes +
                                  m.output_size_in_bytes -
                                  m.alias_size_in_bytes +
                                  m.temp_size_in_bytes)
            row["fits"] = bool(row["total_bytes"] + args.spare_gb * 1e9
                               <= limit)
        except Exception as exc:      # the compiler's refusal is the answer
            row.update(fits=False, refused=str(exc)[:400])
        if row["fits"]:
            best = micro
        print("MICRO " + json.dumps(row), flush=True)
        del engine, model
        gc.collect()
        jax.clear_caches()
    print(json.dumps({"device": info, "largest_that_fits": best}))


if __name__ == "__main__":
    main()
