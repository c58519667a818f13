"""`tiny_copy.make`'s temporary copy of the benchmark with a tiny
configuration of the Brumby family, its serving mix and its cell added
on top, as new files plus appended entries. float32 throughout, so the
limits are those of rounding in another order."""

import json
import os

import tiny_copy

TINY_SIZES = {
    "source": "tests only", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 10,
    "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 512,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "reduced": [],
    "assumed": {"retention": {"degree": 2, "eps": 1e-6,
                              "state_dtype": "float32", "chunk": 8},
                "initializer_range": 0.02},
    "program": {"architecture": "brumby", "param_dtype": "float32"},
}
TINY_SERVE = {
    "kind": "serve_open_arch", "chips": 1,
    "inference": {"max_slots": 4, "prefill_chunk": 16, "sync_every": 2,
                  "max_new_tokens": 40, "max_seq_len": 128},
    "arrivals": {"process": "poisson_conditioned", "rate_per_s": 4.0,
                 "preroll_s": 1.0, "schedule_seed": 5},
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.4,
                      "min": 18, "max": 80},
    "output_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.4,
                      "min": 8, "max": 40},
    "max_total_tokens": 128, "tokens": {"dist": "uniform"}, "drain_s": 10,
    # sound float32 runs read 2e-6 / 2e-7 / 3e-8; the controls below
    # read from 19 times the limit upwards (test_brumby_cell.py)
    "check": {"requests": 4, "live_slots": 4,
              "limits": {"live_logits_rel": 1e-4, "served_gap_max": 2e-5,
                         "served_gap_mean": 2e-6,
                         "state_rows_rel": 1e-4}},
    "control": {"reference_cast": "float8_e4m3fn"},
    "control_program": {"model": {"state_dtype": "bfloat16"}},
}
CELL = "tinyb.tinyb-serve"


def make(tmp_path):
    root = tiny_copy.make(tmp_path)
    for rel, obj in (("configs/tinyb.json", TINY_SIZES),
                     ("traffic/tinyb-serve.json", TINY_SERVE)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tinyb", "source": "tests only",
        "file": "benchmark/configs/tinyb.json", "reduced": [],
        "why": "tests"})
    bench["workloads"].append({
        "name": CELL, "config": "tinyb", "traffic": "tinyb-serve",
        "chips": 1, "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "brumby-14b.serve-longdoc-steady" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
