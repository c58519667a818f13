"""`tiny_copy.make`'s temporary copy of the benchmark with a tiny
configuration of the `phi4flash` family (Phi-4-mini-flash-reasoning),
its serving mix and its cell added on top, as new files plus appended
entries. float32 throughout, so the limits are those of rounding in
another order. Eight layers (two self-decoder periods, the middle one,
one cross period), a window of 12 over pages of 4, contexts up to 128:
every prompt is longer than the window and than two pages, and the
rings turn several times."""

import json
import os

import tiny_copy

TINY_SIZES = {
    "source": "tests only", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 8, "num_attention_heads": 8,
    "num_key_value_heads": 4, "vocab_size": 512,
    "max_position_embeddings": 256, "layer_norm_eps": 1e-5,
    "sliding_window": 12, "mb_per_layer": 2, "tie_word_embeddings": True,
    "reduced": [],
    "assumed": {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
                "mamba_dt_rank": 4, "initializer_range": 0.02,
                "subnorm_eps": 1e-5, "state_dtype": "float32"},
    "program": {"architecture": "phi4flash", "param_dtype": "float32"},
}
TINY_SERVE = {
    "kind": "serve_open_arch", "chips": 1,
    "inference": {"max_slots": 4, "prefill_chunk": 16, "sync_every": 2,
                  "max_new_tokens": 40, "max_seq_len": 128,
                  "kv_cache": {"num_pages": 129, "page_size": 4}},
    "arrivals": {"process": "poisson_conditioned", "rate_per_s": 4.0,
                 "preroll_s": 1.0, "schedule_seed": 5},
    "prompt_tokens": {"dist": "lognormal", "median": 50, "sigma": 0.4,
                      "min": 18, "max": 88},
    "output_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.4,
                      "min": 8, "max": 40},
    "max_total_tokens": 128, "tokens": {"dist": "uniform"}, "drain_s": 10,
    # sound float32 runs read about 1e-6 on the logits, the state and
    # the shared rows; the faults of test_phi4flash_cell.py read from
    # ten times a limit upwards
    "check": {"requests": 4, "live_slots": 4,
              "limits": {"live_logits_rel": 1e-4, "served_gap_max": 1e-4,
                         "served_gap_mean": 1e-5, "scan_state_rel": 1e-4,
                         "shared_rows_rel": 1e-5}},
    "control": {"reference_cast": "float8_e4m3fn"},
    "control_program": {"model": {"state_dtype": "bfloat16"}},
}
CELL = "tinyp.tinyp-serve"
FULL_CELL = "phi-4-mini-flash.serve-think-steady"
FULL_CONFIG_NAME = "phi-4-mini-flash"


def make(tmp_path):
    root = tiny_copy.make(tmp_path)
    for rel, obj in (("configs/tinyp.json", TINY_SIZES),
                     ("traffic/tinyp-serve.json", TINY_SERVE)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    config = next(c for c in bench["configs"]
                  if c["name"] == FULL_CONFIG_NAME)
    workload = next(w for w in bench["workloads"] if w["name"] == FULL_CELL)
    bench["configs"].append(dict(
        config, name="tinyp", source="tests only",
        file="benchmark/configs/tinyp.json", why="tests"))
    bench["workloads"].append(dict(
        workload, name=CELL, config="tinyp", traffic="tinyp-serve",
        why="tests"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if FULL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
