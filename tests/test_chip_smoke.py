"""`chip_smoke.py`'s phases at toy size on the 8-device CPU mesh, and
its refusals: no TPU, a failing phase, a parent that holds the chip.

The chip run itself (`python chip_smoke.py` through the chip tool) is
the acceptance check of the phases at full width; these tests keep the
script's logic from rotting between chip runs.
"""

import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the flagship config's shape at toy cost: ZeRO-2, bf16 master-less,
# a throughput window (steps 2..5) inside the 6 steps run
_TOY_DS_CONFIG = {
    "train_micro_batch_size_per_gpu": 1,
    "gradient_accumulation_steps": 1,
    "steps_per_print": 5,
    "bf16": {"enabled": True, "master_weights": False},
    "zero_optimization": {"stage": 2},
    "optimizer": {"type": "AdamW",
                  "params": {"lr": 1e-3, "weight_decay": 0.01}},
}

_TOY_KERNELS = chip_smoke.KernelSizes(
    flash=(1, 128, 2, 64), flash_d128=(1, 128, 1, 128),
    flash_multi_tile=(1, 256, 2, 64), rows=32, hidden=128,
    sparse=(1, 512, 2, 64, 128), moe=(64, 128, 4, 2), dtype="float32")


def test_train_phase_toy():
    seen = chip_smoke.train_phase("gpt2-tiny", 128, steps=6,
                                  ds_config=_TOY_DS_CONFIG,
                                  expect_kernels=())
    assert len(seen["losses"]) == 6
    assert seen["opt_leaf_devices"] == list(range(8))


def test_train_phase_fp32_masters_divide_over_the_mesh():
    """The configuration the four-chip run uses to prove that state
    divides: fp32 masters and moments, nothing whole on device 0."""
    config = dict(_TOY_DS_CONFIG, bf16={"enabled": True})
    seen = chip_smoke.train_phase("gpt2-tiny", 128, steps=6,
                                  ds_config=config, expect_kernels=())
    per_device = seen["per_device_bytes"]
    assert max(per_device) <= 1.10 * min(per_device), per_device


def test_train_phase_demands_its_kernels():
    """Off the chip the step holds no Mosaic kernel; the phase must say
    so rather than pass on the interpreter or the XLA attention."""
    with pytest.raises(chip_smoke.SmokeFailure, match="no Mosaic kernel"):
        chip_smoke.train_phase("gpt2-tiny", 128, steps=6,
                               ds_config=_TOY_DS_CONFIG)


def test_serve_phase_toy():
    seen = chip_smoke.serve_phase(
        "gpt2-tiny", 128, prompt_lens=(5, 37, 20), max_new=(4, 6, 3),
        prefill_chunk=16, parity_prompt=30, parity_steps=2)
    assert seen["requests"] == 3


def test_kernel_phase_toy():
    errors = chip_smoke.kernel_phase(_TOY_KERNELS, interpret=True)
    assert set(errors) == {c.name for c in chip_smoke.kernel_cases()}


def test_main_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "platform=cpu" in proc.stdout
    # it stopped before building a model, and printed no result
    assert "train:" not in proc.stdout
    assert '"ok"' not in proc.stdout


def test_a_failing_phase_fails_the_run(monkeypatch, capsys):
    """No phase result is caught: the failure leaves `main` as an
    exception (a non-zero exit) and no result line is printed."""
    def broken_phase():
        raise chip_smoke.SmokeFailure("made to fail")

    monkeypatch.setattr(chip_smoke, "describe_device", lambda: {
        "platform": "tpu", "kind": "test double", "count": 1})
    monkeypatch.setattr(chip_smoke, "train_phase", broken_phase)
    with pytest.raises(chip_smoke.SmokeFailure, match="made to fail"):
        chip_smoke.main()
    assert '"ok"' not in capsys.readouterr().out


def test_importing_the_package_takes_no_chip():
    """One process per chip: a launcher or parent that only imports the
    package must leave the device to its child."""
    code = (
        "import deepspeed_tpu, deepspeed_tpu.inference, "
        "deepspeed_tpu.moe, deepspeed_tpu.launcher.runner\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, cwd=REPO,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
