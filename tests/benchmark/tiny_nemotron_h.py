"""`tiny_copy.make`'s temporary copy of the benchmark with a tiny
configuration of the `nemotron_h` family (Nemotron-3-Nano), its serving
mix and its cell added on top, as new files plus appended entries.
float32 throughout, so the limits are those of rounding in another
order. The pattern is the full cell's 16 letters; the chip's share is
HALF, 8 of 16 experts (a token picks 4), stored 32 columns wide where
the "published" width is 24; contexts reach 128 over pages of 8."""

import json
import os

import tiny_copy

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
TINY_SIZES = {
    "source": "tests only", "hidden_size": 64, "num_hidden_layers": 16,
    "hybrid_override_pattern": PATTERN[:16], "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 512,
    "max_position_embeddings": 256, "layer_norm_epsilon": 1e-5,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "n_routed_experts": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "first_expert": 0,
    "reduced": ["num_hidden_layers", "hybrid_override_pattern",
                "n_routed_experts", "vocab_size"],
    "published": {"num_hidden_layers": 52,
                  "hybrid_override_pattern": PATTERN,
                  "n_routed_experts": 16, "vocab_size": 1024},
    "assumed": {"initializer_range": 0.02, "ssm_state_dtype": "float32"},
    "program": {"architecture": "nemotron_h", "param_dtype": "float32",
                "expert_width_stored": 32},
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
    # sound float32 runs read about 1e-6 on the logits and on the state
    # and agree on every pick; the faults of test_nemotron_h_cell.py
    # read from ten times a limit upwards
    "check": {"requests": 4, "live_slots": 4,
              "limits": {"live_logits_rel": 1e-4, "served_gap_max": 1e-4,
                         "served_gap_mean": 1e-5, "ssm_state_rel": 1e-4,
                         "router_picks_agree": 0.99}},
    "control": {"reference_cast": "float8_e4m3fn"},
    "control_program": {"model": {"ssm_state_dtype": "bfloat16"}},
}
CELL = "tinyn.tinyn-serve"
FULL_CELL = "nemotron-3-nano-30b.serve-turns-steady"

# What `BENCHMARK.json` holds of the full cell, as PR 45 appended it
# (`test_nemotron_h_cell.py` holds the file to these): the tiny copy
# below joins the lists the full cell is on.
FULL_CONFIG = {
    "name": "nemotron-3-nano-30b",
    "source": "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-"
              "A3B-BF16/blob/main/config.json",
    "file": "benchmark/configs/nemotron-3-nano-30b.json",
    "reduced": ["num_hidden_layers", "hybrid_override_pattern",
                "n_routed_experts", "vocab_size"],
    "why": "every layer ONE of Mamba-2, 128 ungated relu2 experts (6 a "
           "token, a shared one) or 32/2-head attention, by a pattern "
           "string; this chip's 64 experts (1 of 2 chips a layer), the "
           "first 16 of 52 layers"}
NEW_PER_LAYER = [
    {"name": "nemotron_h_moe_held_roofline", "unit": "%",
     "better": "higher", "source": "device_trace",
     "layer": "kernels (moe)", "moves": "itl_mean_ms"},
    {"name": "nemotron_h_moe_held_touched_share", "unit": "%",
     "better": "higher", "source": "program_counter", "layer": "model",
     "moves": "itl_mean_ms"},
    {"name": "nemotron_h_ssm_decode_roofline", "unit": "%",
     "better": "higher", "source": "device_trace",
     "layer": "kernels (ssm)", "moves": "itl_mean_ms"}]
# the accepted metrics whose readers find something to read in this
# cell as they stand (each read on a traced run on the chip, PR 45)
LISTS_THE_CELL = (
    "itl_mean_ms", "serve_tokens_per_s", "compiles_in_window.serve",
    "ttft_observed_mean_ms", "ttft_p90_ms", "itl_p95_ms",
    "peak_hbm_gb.serve", "decode_iter_ms", "prefill_chunk_ms",
    "queue_wait_mean_ms", "slots_occupied_mean", "device_idle_share.serve",
    "kv_gather_time_share.serve", "attention_time_share.serve",
    "weight_matmul_time_share.serve", "unscoped_time_share.serve",
    "program_temp_gb.serve", "ssm_state_time_share.serve",
    "moe_time_share.serve", "host_iter_ms.serve", "host_exposed_ms.serve",
    "readback_exposed_ms.serve", "bookkeeping_exposed_ms.serve",
    "dispatch_exposed_ms.serve")


def make(tmp_path):
    root = tiny_copy.make(tmp_path)
    for rel, obj in (("configs/tinyn.json", TINY_SIZES),
                     ("traffic/tinyn-serve.json", TINY_SERVE)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append(dict(
        FULL_CONFIG, name="tinyn", source="tests only",
        file="benchmark/configs/tinyn.json", why="tests"))
    bench["workloads"].append({
        "name": CELL, "config": "tinyn", "traffic": "tinyn-serve",
        "chips": 1, "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if FULL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
