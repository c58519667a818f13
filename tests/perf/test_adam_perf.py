"""Standalone CPU-Adam perf guard (counterpart of the reference's
`tests/perf/adam_test1.py`, which times `deepspeed.ops.adam.DeepSpeedCPUAdam`
on a bare parameter blob).

The ZeRO-Offload path lives or dies by the native OpenMP/AVX CPU-Adam
kernel: the host optimizer step sits on the critical path between D2H
grads and H2D params, and a silent regression to the numpy reference
implementation (broken native build, wheel without the extension,
ctypes loader change) would tank offload throughput without failing a
single numerics test. This guard times native vs numpy at the
reference's two larger sizes (the smallest only shows that the native
kernel ran) and asserts the native kernel keeps a >= 5x lead
(measured 100-165x on the CI container; the reference observed ~11x on
its hardware — 5x leaves headroom for a loaded host while still
catching "accidentally running numpy").

Skips (not passes) when the native build is unavailable, so the
report distinguishes "no native kernel here" from "native is slow"."""

import time

import numpy as np
import pytest

from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam

MIN_SPEEDUP = 5.0


def _native_or_skip(n):
    try:
        opt = DeepSpeedCPUAdam(n, lr=1e-3, use_native=True)
    except Exception as e:  # loader/build errors
        pytest.skip(f"native cpu_adam unavailable: {e}")
    if not getattr(opt, "native", True):
        pytest.skip("native cpu_adam unavailable")
    return opt


def _blobs(n):
    rng = np.random.RandomState(7)
    p0 = rng.randn(n).astype(np.float32)
    g = rng.randn(n).astype(np.float32)
    nat = _native_or_skip(n)
    ref = DeepSpeedCPUAdam(n, lr=1e-3, use_native=False)
    pn, pr = p0.copy(), p0.copy()
    nat.step(pn, g)  # warmup: page-in, OpenMP thread-pool spin-up
    ref.step(pr, g)
    return nat, ref, pn, pr, g


def _assert_native_speedup(n, reps=5):
    nat, ref, pn, pr, g = _blobs(n)
    # the two sides turn about, each at its best of several: a burst of
    # other work on the host (the suite runs under several workers)
    # then falls on both, not on one side's whole measurement
    t_nat = t_ref = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        nat.step(pn, g)
        t1 = time.perf_counter()
        ref.step(pr, g)
        t_nat = min(t_nat, t1 - t0)
        t_ref = min(t_ref, time.perf_counter() - t1)
    speedup = t_ref / t_nat
    assert speedup >= MIN_SPEEDUP, (
        f"native CPU-Adam at {n/1e6:.0f}M params: {t_nat*1e3:.2f} ms vs "
        f"numpy {t_ref*1e3:.2f} ms — only {speedup:.1f}x (need >= "
        f"{MIN_SPEEDUP}x); the native build has likely regressed or the "
        "offload path silently fell back to the numpy reference")


def test_native_adam_runs_at_1m():
    """The reference's smallest size holds no wall-clock ratio: one
    native step is a fraction of a millisecond across the OpenMP
    threads, and beside other test workers a descheduled thread at a
    barrier costs it milliseconds (4-6x read here under load, 100x
    alone). What this size can say whatever the host does: the native
    kernel is what ran, every thread's share of the blob included,
    and it took numpy's step."""
    nat, ref, pn, pr, g = _blobs(1_000_000)
    assert nat.native and not ref.native
    for _ in range(3):
        nat.step(pn, g)
        ref.step(pr, g)
    assert nat.step_count == ref.step_count == 4
    np.testing.assert_allclose(pn, pr, atol=1e-5)


def test_native_adam_speedup_10m():
    _assert_native_speedup(10_000_000)


@pytest.mark.slow
def test_native_adam_speedup_100m():
    # the reference's largest leg; numpy needs ~3 s/step here, so this
    # stays in the slow tier
    _assert_native_speedup(100_000_000, reps=3)
