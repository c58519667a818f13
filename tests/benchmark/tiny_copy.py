"""A temporary copy of the benchmark with a tiny configuration, a tiny
training mix, a tiny serving mix and a dummy per-layer metric added as
NEW files plus appended entries, the way a later PR adds them. The
tests drive the harness on it on the CPU."""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_SIZES = {
    "source": "tests only", "n_layer": 2, "n_embd": 64, "n_head": 4,
    "n_positions": 128, "vocab_size": 512, "reduced": [], "assumed": {},
    "program": {"preset": "gpt2-tiny", "param_dtype": "float32",
                "remat_policy": None},
}
TINY_TRAIN = {
    "kind": "train", "chips": 1, "seq_len": 128,
    "ds_config": {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1, "steps_per_print": 1000,
        "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW", "params": {
            "lr": 1e-3, "betas": [0.9, 0.95], "weight_decay": 0.01}},
        "scheduler": {"type": "WarmupLR", "params": {
            "warmup_min_lr": 0.0, "warmup_max_lr": 1e-3,
            "warmup_num_steps": 100}}},
    "tokens": {"dist": "zipf", "exponent": 1.0},
    "check": {"steps": 2, "reference_rows_per_block": 1,
              "limits": {"loss_abs": 1e-4, "grad_norm_rel": 0.1,
                         "dp_along_mu_rel": 0.3}},
    "control": {"reference_cast": "float8_e4m3fn"},
}
TINY_SERVE = {
    "kind": "serve_open", "chips": 1,
    "inference": {"max_slots": 4, "prefill_chunk": 16, "sync_every": 2,
                  "max_new_tokens": 48, "max_seq_len": 128,
                  "kv_cache": {"num_pages": 33, "page_size": 16}},
    "arrivals": {"process": "poisson_conditioned", "rate_per_s": 4.0,
                 "preroll_s": 1.0, "schedule_seed": 5},
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                      "min": 4, "max": 80},
    "output_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 8, "max": 48},
    "max_total_tokens": 128, "tokens": {"dist": "uniform"}, "drain_s": 10,
    "check": {"requests": 8, "limits": {"served_gap_max": 1e-3,
                                        "served_gap_mean": 1e-4,
                                        "live_logits_rel": 0.012}},
    "control": {"reference_cast": "float8_e4m3fn"},
    "control_program": {"inference": {"weight_bits": 8}},
}
DUMMY_METRIC = '''"""A dummy per-layer metric, added as a new file."""


def read(ctx):
    return 42.0
'''


def make(tmp_path):
    """Copies `benchmark/` and `BENCHMARK.json` into `tmp_path`, adds
    the tiny files and entries, and returns the root of the copy."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    write = lambda rel, obj: json.dump(
        obj, open(os.path.join(root, "benchmark", rel), "w"))
    write("configs/tiny.json", TINY_SIZES)
    write("traffic/tiny-train.json", TINY_TRAIN)
    write("traffic/tiny-serve.json", TINY_SERVE)
    with open(os.path.join(root, "benchmark", "metrics",
                           "dummy_metric.py"), "w") as f:
        f.write(DUMMY_METRIC)
    # the table of peaks knows no CPU: the copy gets a made-up row
    peaks_file = os.path.join(root, "benchmark", "peaks.json")
    with open(peaks_file) as f:
        peaks = json.load(f)
    peaks["cpu"] = dict(peaks["TPU v5 lite"])
    with open(peaks_file, "w") as f:
        json.dump(peaks, f)
    bench["configs"].append({
        "name": "tiny", "source": "tests only",
        "file": "benchmark/configs/tiny.json", "reduced": [],
        "why": "tests"})
    cells = ["tiny.tiny-train", "tiny.tiny-serve"]
    for cell, mix in zip(cells, ("tiny-train", "tiny-serve")):
        bench["workloads"].append({
            "name": cell, "config": "tiny", "traffic": mix, "chips": 1,
            "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kind = "train" if any("train" in w for w in m["workloads"]) \
                else "serve"
            m["workloads"].append("tiny.tiny-" + kind)
    bench["per_layer"].append({
        "name": "dummy_metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry", "moves": "setup_s",
        "workloads": cells})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def point_harness_at(monkeypatch, root):
    from benchmark import harness
    monkeypatch.setattr(harness, "REPO", root)
    monkeypatch.setattr(harness, "HERE", os.path.join(root, "benchmark"))
    monkeypatch.setattr(harness, "TRACE_DIR",
                        os.path.join(root, ".bench_trace"))
    return harness
