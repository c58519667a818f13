"""Model-level convergence/regression harness (parity target: ref
`tests/model/Megatron_GPT2/run_func_test.py` — run REAL example scripts
under real configs as subprocesses, grep the loss trajectory, and
compare (a) across configs and (b) against checked-in baseline curves).

Runs `examples/gpt2_train.py` / `examples/bert_pretrain.py` on the
8-device virtual CPU mesh (JAX_PLATFORMS=cpu). Baselines live in
`tests/model/baselines/*.json`; regenerate with
`python tests/model/test_model_regression.py --regen` after an
intentional numerics change.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BASELINE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "baselines")
STEPS = 60

GPT2_BASE_CONFIG = {
    # per chip: 8 rows a micro-step over the 8-device mesh, the batch
    # the checked-in baselines were recorded with
    "train_micro_batch_size_per_gpu": 1,
    "gradient_accumulation_steps": 2,
    "steps_per_print": 50,
    "gradient_clipping": 1.0,
    "optimizer": {"type": "AdamW",
                  "params": {"lr": 3e-3, "betas": [0.9, 0.95],
                             "weight_decay": 0.01}},
    "scheduler": {"type": "WarmupLR",
                  "params": {"warmup_min_lr": 0.0,
                             "warmup_max_lr": 3e-3,
                             "warmup_num_steps": 10}},
}

BERT_BASE_CONFIG = {
    "train_micro_batch_size_per_gpu": 8,
    "gradient_accumulation_steps": 2,
    "steps_per_print": 50,
    "optimizer": {"type": "Lamb",
                  "params": {"lr": 2e-3}},
}


def run_example(script, model, config, steps=STEPS, seq_len=64, seed=42,
                tmp_dir="/tmp"):
    """Run an example script as a subprocess; return its loss
    trajectory (list of floats, one per step)."""
    cfg_path = os.path.join(tmp_dir, f"ds_config_{model}.json")
    with open(cfg_path, "w") as f:
        json.dump(config, f)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script),
         "--model", model, "--seq-len", str(seq_len),
         "--steps", str(steps), "--seed", str(seed),
         "--num-batches", "2",   # fixed learnable set -> loss must fall
         "--deepspeed", "--deepspeed_config", cfg_path],
        capture_output=True, text=True, timeout=1200, env=env, cwd=REPO)
    m = re.search(r"LM loss trajectory: ([\d .eE+-]+)", proc.stdout)
    assert proc.returncode == 0 and m, \
        f"{script} failed\nstdout: {proc.stdout[-1500:]}\n" \
        f"stderr: {proc.stderr[-1500:]}"
    return [float(x) for x in m.group(1).split()]


def _check_against_baseline(name, traj):
    path = os.path.join(BASELINE_DIR, name + ".json")
    with open(path) as f:
        base = json.load(f)["trajectory"]
    assert len(traj) == len(base), (len(traj), len(base))
    # convergence shape: start equal (same init), end within tolerance,
    # running means track (pointwise noise from dropout-free synthetic
    # data is tiny; tolerances still leave room for XLA version drift)
    np.testing.assert_allclose(traj[0], base[0], rtol=1e-3)
    assert abs(traj[-1] - base[-1]) < max(0.1 * abs(base[-1]), 0.15), \
        (traj[-1], base[-1])
    m_new = np.mean(traj[len(traj) // 2:])
    m_base = np.mean(base[len(base) // 2:])
    assert abs(m_new - m_base) < max(0.1 * abs(m_base), 0.15), \
        (m_new, m_base)


@pytest.mark.slow
def test_gpt2_func_zero0_converges_and_matches_baseline(tmp_path):
    cfg = dict(GPT2_BASE_CONFIG)
    cfg["zero_optimization"] = {"stage": 0}
    traj = run_example("gpt2_train.py", "gpt2-tiny", cfg,
                       tmp_dir=str(tmp_path))
    assert traj[-1] < traj[0] * 0.7, (traj[0], traj[-1])
    _check_against_baseline("gpt2_tiny_zero0", traj)


@pytest.mark.slow
def test_gpt2_func_zero2_bf16_matches_zero0_fp32_shape(tmp_path):
    """ZeRO-2 + bf16 must follow the same loss curve as ZeRO-0 fp32 at
    model level (bf16 rounding gives pointwise drift; the curve SHAPE
    and endpoint must agree) — the reference's cross-config check
    (run_func_test.py compares ZeRO configs against megatron)."""
    cfg0 = dict(GPT2_BASE_CONFIG)
    cfg0["zero_optimization"] = {"stage": 0}
    t0 = run_example("gpt2_train.py", "gpt2-tiny", cfg0,
                     tmp_dir=str(tmp_path))
    cfg2 = dict(GPT2_BASE_CONFIG)
    cfg2["zero_optimization"] = {"stage": 2}
    cfg2["bf16"] = {"enabled": True}
    t2 = run_example("gpt2_train.py", "gpt2-tiny", cfg2,
                     tmp_dir=str(tmp_path))
    np.testing.assert_allclose(t0[0], t2[0], rtol=5e-2)
    assert abs(t0[-1] - t2[-1]) < max(0.15 * abs(t0[-1]), 0.2), \
        (t0[-1], t2[-1])


@pytest.mark.slow
def test_gpt2_func_bf16_masterless_sr(tmp_path):
    """The bf16 master-less (stochastic rounding) flagship config must
    converge at model level too."""
    cfg = dict(GPT2_BASE_CONFIG)
    cfg["zero_optimization"] = {"stage": 2}
    cfg["bf16"] = {"enabled": True, "master_weights": False}
    traj = run_example("gpt2_train.py", "gpt2-tiny", cfg,
                       tmp_dir=str(tmp_path))
    assert traj[-1] < traj[0] * 0.7, (traj[0], traj[-1])
    _check_against_baseline("gpt2_tiny_sr", traj)


@pytest.mark.slow
def test_bert_func_converges_and_matches_baseline(tmp_path):
    traj = run_example("bert_pretrain.py", "bert-tiny",
                       dict(BERT_BASE_CONFIG), tmp_dir=str(tmp_path))
    assert traj[-1] < traj[0] * 0.9, (traj[0], traj[-1])
    _check_against_baseline("bert_tiny_lamb", traj)


def _regen():
    os.makedirs(BASELINE_DIR, exist_ok=True)
    jobs = []
    cfg = dict(GPT2_BASE_CONFIG)
    cfg["zero_optimization"] = {"stage": 0}
    jobs.append(("gpt2_tiny_zero0", "gpt2_train.py", "gpt2-tiny", cfg))
    cfg = dict(GPT2_BASE_CONFIG)
    cfg["zero_optimization"] = {"stage": 2}
    cfg["bf16"] = {"enabled": True, "master_weights": False}
    jobs.append(("gpt2_tiny_sr", "gpt2_train.py", "gpt2-tiny", cfg))
    jobs.append(("bert_tiny_lamb", "bert_pretrain.py", "bert-tiny",
                 dict(BERT_BASE_CONFIG)))
    for name, script, model, config in jobs:
        traj = run_example(script, model, config)
        with open(os.path.join(BASELINE_DIR, name + ".json"), "w") as f:
            json.dump({"steps": len(traj), "trajectory": traj}, f)
        print(name, "->", traj[0], "...", traj[-1])


if __name__ == "__main__" and "--regen" in sys.argv:
    _regen()
