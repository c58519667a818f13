"""`tiny_copy.make`'s temporary copy of the benchmark with a tiny
configuration of the Falcon-H1 family, its serving mix and its cell
added on top, as new files plus appended entries. float32 throughout,
so the limits are those of rounding in another order. The muP
multipliers are the published ones."""

import json
import os

import tiny_copy

TINY_SIZES = {
    "source": "tests only", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 6,
    "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 512,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 100000000000,
    "mamba_d_ssm": 64, "mamba_n_heads": 8, "mamba_d_head": 8,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 8,
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "reduced": [],
    "assumed": {"initializer_range": 0.02, "ssm_state_dtype": "float32"},
    "program": {"architecture": "falcon_h1", "param_dtype": "float32"},
}
TINY_SERVE = {
    "kind": "serve_open_arch", "chips": 1,
    "inference": {"max_slots": 4, "prefill_chunk": 16, "sync_every": 2,
                  "max_new_tokens": 40, "max_seq_len": 128,
                  "kv_cache": {"num_pages": 65, "page_size": 8}},
    "arrivals": {"process": "poisson_conditioned", "rate_per_s": 4.0,
                 "preroll_s": 1.0, "schedule_seed": 5},
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.4,
                      "min": 18, "max": 80},
    "output_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.4,
                      "min": 8, "max": 40},
    "max_total_tokens": 128, "tokens": {"dist": "uniform"}, "drain_s": 10,
    # sound float32 runs read about 1e-6 on every number; the faults of
    # test_falcon_h1_cell.py read from ten times a limit upwards
    "check": {"requests": 4, "live_slots": 4,
              "limits": {"live_logits_rel": 1e-4, "served_gap_max": 1e-4,
                         "served_gap_mean": 1e-5, "ssm_state_rel": 1e-4}},
    "control": {"reference_cast": "float8_e4m3fn"},
    "control_program": {"model": {"ssm_state_dtype": "bfloat16"}},
}
CELL = "tinyf.tinyf-serve"
FULL_CELL = "falcon-h1-34b.serve-longctx-steady"


def make(tmp_path):
    root = tiny_copy.make(tmp_path)
    for rel, obj in (("configs/tinyf.json", TINY_SIZES),
                     ("traffic/tinyf-serve.json", TINY_SERVE)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tinyf", "source": "tests only",
        "file": "benchmark/configs/tinyf.json", "reduced": [],
        "why": "tests"})
    bench["workloads"].append({
        "name": CELL, "config": "tinyf", "traffic": "tinyf-serve",
        "chips": 1, "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if FULL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
