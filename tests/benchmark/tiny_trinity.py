"""`tiny_copy.make`'s temporary copy of the benchmark with a tiny
configuration of the `afmoe` family (Trinity), its serving mix and its
cell added on top, as new files plus appended entries. float32
throughout, so the limits are those of rounding in another order. The
window is 24 tokens over pages of 8 and contexts reach 128: pages are
released and the ring wraps several times in every request."""

import json
import os

import tiny_copy

SLIDING, FULL = "sliding_attention", "full_attention"
TINY_SIZES = {
    "source": "tests only", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "num_dense_layers": 1, "num_attention_heads": 6,
    "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 512,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "sliding_window": 24, "num_experts": 16,
    "num_experts_per_tok": 4, "num_shared_experts": 1,
    "route_scale": 2.826, "route_norm": True, "score_func": "sigmoid",
    "mup_enabled": True,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 2,
    "kept_layers": [1, 4, 5, 6, 7],
    "reduced": ["num_hidden_layers", "num_dense_layers"],
    "published": {"num_hidden_layers": 8, "num_dense_layers": 2},
    "assumed": {"initializer_range": 0.02},
    "program": {"architecture": "afmoe", "param_dtype": "float32"},
}
TINY_SERVE = {
    "kind": "serve_open_arch", "chips": 1,
    "inference": {"max_slots": 4, "prefill_chunk": 16, "sync_every": 2,
                  "max_new_tokens": 40, "max_seq_len": 128,
                  "kv_cache": {"num_pages": 65, "page_size": 8}},
    "arrivals": {"process": "poisson_conditioned", "rate_per_s": 4.0,
                 "preroll_s": 1.0, "schedule_seed": 5},
    "prompt_tokens": {"dist": "lognormal", "median": 50, "sigma": 0.4,
                      "min": 18, "max": 88},
    "output_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.4,
                      "min": 8, "max": 40},
    "max_total_tokens": 128, "tokens": {"dist": "uniform"}, "drain_s": 10,
    # sound float32 runs read about 1e-6 on the logits and agree on
    # every pick; the faults of test_trinity_cell.py read from ten
    # times a limit upwards
    "check": {"requests": 4, "live_slots": 4,
              "limits": {"live_logits_rel": 1e-4, "served_gap_max": 1e-4,
                         "served_gap_mean": 1e-5,
                         "router_picks_agree": 0.99}},
    "control": {"reference_cast": "float8_e4m3fn"},
}
CELL = "tinyt.tinyt-serve"
FULL_CELL = "trinity-mini.serve-reason-steady"


def make(tmp_path):
    root = tiny_copy.make(tmp_path)
    for rel, obj in (("configs/tinyt.json", TINY_SIZES),
                     ("traffic/tinyt-serve.json", TINY_SERVE)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tinyt", "source": "tests only",
        "file": "benchmark/configs/tinyt.json",
        "reduced": TINY_SIZES["reduced"], "why": "tests"})
    bench["workloads"].append({
        "name": CELL, "config": "tinyt", "traffic": "tinyt-serve",
        "chips": 1, "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if FULL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
