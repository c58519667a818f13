"""ThroughputTimer samples/sec accounting (ISSUE 2 satellite).

The audit point: `avg_samples_per_sec` multiplies
`batch_size (micro per worker) * num_workers`, while `stop(count=...)`
counts MICROBATCHES — these units must cancel so that gas>1 fused steps
(count=gas) and dp>1 both report train_batch_size * steps / elapsed.
These tests pin that with a fake clock, and the pre-warmup return value
(0.0, not -inf).
"""

import pytest

import deepspeed_tpu.utils.timer as timer_mod
from deepspeed_tpu.utils.timer import ThroughputTimer


class _FakeTime:
    """Deterministic stand-in for the `time` module inside timer.py."""

    def __init__(self):
        # non-zero start: the timer uses start_time == 0 as its
        # "window not yet open" sentinel
        self.now = 1000.0

    def time(self):
        return self.now

    def advance(self, dt):
        self.now += dt


@pytest.fixture
def fake_time(monkeypatch):
    ft = _FakeTime()
    monkeypatch.setattr(timer_mod, "time", ft)
    return ft


def _run_steps(t, fake_time, n, count, step_seconds):
    for _ in range(n):
        t.start()
        fake_time.advance(step_seconds)
        t.stop(count=count)


def test_avg_samples_per_sec_prewarmup_is_zero():
    t = ThroughputTimer(batch_size=4, num_workers=2)
    assert t.avg_samples_per_sec() == 0.0
    t.start()
    t.stop(count=1)   # still inside warmup (start_step=2)
    assert t.avg_samples_per_sec() == 0.0


def test_samples_per_sec_gas_gt_1(fake_time):
    """One fused step = gas microbatches (stop(count=gas)): reported
    rate must be micro_bs * gas / step_time (dp=1)."""
    micro_bs, gas, step_s = 2, 4, 0.5
    logged = []
    t = ThroughputTimer(batch_size=micro_bs, num_workers=1,
                        start_step=2, steps_per_output=gas * 2,
                        logging_fn=logged.append)
    # step 1 ends warmup (gsc=4 >= 2) and opens the window
    _run_steps(t, fake_time, 1, gas, step_s)
    assert t.avg_samples_per_sec() == 0.0   # window open, nothing fenced
    # two more steps; gsc hits 8 then 12 → reports at both
    _run_steps(t, fake_time, 2, gas, step_s)
    expected = micro_bs * gas / step_s      # 16 samples/sec
    assert t.avg_samples_per_sec() == pytest.approx(expected)
    assert logged, "steps_per_output fence did not log"


def test_samples_per_sec_dp_gt_1(fake_time):
    """dp>1 at gas=1: every worker consumes micro_bs samples per
    microbatch tick → micro_bs * dp / step_time."""
    micro_bs, dp, step_s = 3, 4, 0.25
    t = ThroughputTimer(batch_size=micro_bs, num_workers=dp,
                        start_step=2, steps_per_output=2,
                        logging_fn=lambda *_: None)
    _run_steps(t, fake_time, 2, 1, step_s)   # warmup + window open
    _run_steps(t, fake_time, 4, 1, step_s)
    expected = micro_bs * dp / step_s        # 48 samples/sec
    assert t.avg_samples_per_sec() == pytest.approx(expected)


def test_samples_per_sec_gas_and_dp(fake_time):
    """gas>1 AND dp>1 combined: rate = train_batch_size / step_time
    where train_batch_size = micro_bs * gas * dp."""
    micro_bs, gas, dp, step_s = 2, 3, 4, 1.0
    t = ThroughputTimer(batch_size=micro_bs, num_workers=dp,
                        start_step=2, steps_per_output=gas,
                        logging_fn=lambda *_: None)
    _run_steps(t, fake_time, 1, gas, step_s)   # warmup + window open
    _run_steps(t, fake_time, 3, gas, step_s)
    expected = micro_bs * gas * dp / step_s    # 24 samples/sec
    assert t.avg_samples_per_sec() == pytest.approx(expected)


def test_mid_window_steps_not_counted_until_fence(fake_time):
    t = ThroughputTimer(batch_size=2, num_workers=1, start_step=2,
                        steps_per_output=100,
                        logging_fn=lambda *_: None)
    _run_steps(t, fake_time, 2, 1, 0.5)   # warmup + window open
    _run_steps(t, fake_time, 5, 1, 0.5)   # all mid-window (no fence)
    # unfenced in-flight steps are not claimed as measured throughput
    assert t.avg_samples_per_sec() == 0.0
