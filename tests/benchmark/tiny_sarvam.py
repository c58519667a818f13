"""`tiny_copy.make`'s temporary copy of the benchmark with a tiny
configuration of the `sarvam_mla` family (Sarvam-105B), its serving
mix and its cell added on top, as new files plus appended entries.
float32 throughout, so the limits are those of rounding in another
order. The chip's share is 4 of 16 experts (a token picks 4: most rows
have a pick that is not held) and contexts reach 128 over pages of 8:
every request's table holds a dozen pages."""

import json
import os

import tiny_copy

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "deepseek_yarn"}
TINY_SIZES = {
    "source": "tests only", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "q_head_dim": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "kv_lora_rank": 32, "head_dim": 40, "vocab_size": 512,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "rope_scaling": YARN, "num_experts": 4,
    "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "moe_router_enable_expert_bias": True,
    "use_qk_norm": True, "first_expert": 0,
    "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
    "published": {"num_hidden_layers": 8, "num_experts": 16,
                  "vocab_size": 2048},
    "assumed": {"initializer_range": 0.02},
    "program": {"architecture": "sarvam_mla", "param_dtype": "float32"},
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
    # sound float32 runs read about 1e-6 on the logits and on the
    # latent rows and agree on every pick; the faults of
    # test_sarvam_cell.py read from ten times a limit upwards
    "check": {"requests": 4, "live_slots": 4,
              "limits": {"live_logits_rel": 1e-4, "served_gap_max": 1e-4,
                         "served_gap_mean": 1e-5,
                         "router_picks_agree": 0.99,
                         "latent_rows_rel": 1e-4,
                         "latent_rows_mean_rel": 1e-5}},
    "control": {"reference_cast": "float8_e4m3fn"},
}
CELL = "tinys.tinys-serve"
FULL_CELL = "sarvam-105b.serve-assist-steady"

# What `BENCHMARK.json` holds of the full cell, as PR 39 appended it
# (`test_sarvam_cell.py` holds the file to these): the tiny copy below
# joins the lists the full cell is on.
FULL_CONFIG = {
    "name": "sarvam-105b",
    "source": "https://huggingface.co/sarvamai/sarvam-105b/blob/main/"
              "config.json",
    "file": "benchmark/configs/sarvam-105b.json",
    "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
    "why": "latent attention: 64 heads over ONE 576-value cache row a "
           "token (absorbed in decode); 128 experts of width 2048 + a "
           "shared one, this chip's 32 of them (1 of 4 chips a layer); "
           "1 dense + 4 of 32 layers"}
FULL_WORKLOAD = {
    "name": FULL_CELL, "config": "sarvam-105b",
    "traffic": "serve-assist-steady", "chips": 1,
    "why": "open loop 1.05/s (0.7 of knee 1.5), prompts ~3k (512-8k), "
           "answers ~1.3k (512-2.5k), 96 slots (~32 live), 13.2 GB: 4x32 "
           "held experts read a step; dispatch sees 1/4 of its rows"}
NEW_PER_LAYER = [
    {"name": "mla_decode_roofline", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels (latent decode)",
     "moves": "itl_mean_ms"},
    {"name": "mla_absorb_time_share.serve", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "model", "moves": "itl_mean_ms"},
    {"name": "moe_held_touched_share", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "model", "moves": "itl_mean_ms"},
    {"name": "moe_held_roofline", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels (moe)",
     "moves": "itl_mean_ms"}]
# the accepted metrics whose readers find something to read in this
# cell as they stand (each read on a traced run on the chip, PR 39)
LISTS_THE_CELL = (
    "itl_mean_ms", "serve_tokens_per_s", "compiles_in_window.serve",
    "ttft_observed_mean_ms", "ttft_p90_ms", "itl_p95_ms",
    "peak_hbm_gb.serve", "decode_iter_ms", "prefill_chunk_ms",
    "queue_wait_mean_ms", "slots_occupied_mean", "device_idle_share.serve",
    "kv_pool_carry_time_share.serve", "kv_gather_time_share.serve",
    "attention_time_share.serve", "weight_matmul_time_share.serve",
    "unscoped_time_share.serve", "program_temp_gb.serve",
    "moe_time_share.serve", "host_iter_ms.serve", "host_exposed_ms.serve",
    "readback_exposed_ms.serve", "bookkeeping_exposed_ms.serve",
    "dispatch_exposed_ms.serve")


def make(tmp_path):
    root = tiny_copy.make(tmp_path)
    for rel, obj in (("configs/tinys.json", TINY_SIZES),
                     ("traffic/tinys-serve.json", TINY_SERVE)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append(dict(
        FULL_CONFIG, name="tinys", source="tests only",
        file="benchmark/configs/tinys.json", why="tests"))
    bench["workloads"].append(dict(
        FULL_WORKLOAD, name=CELL, config="tinys", traffic="tinys-serve",
        why="tests"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if FULL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
