"""Wall-clock + throughput timers.

TPU-native analogue of `deepspeed/utils/timer.py:19,97`. Where the reference
fences with `torch.cuda.synchronize()`, we fence with
`jax.block_until_ready` on the outputs of the work being timed — XLA
dispatch is async exactly like CUDA streams, and JAX has no
device-wide synchronize: only waiting on a result waits for the device.
"""

import time

from deepspeed_tpu.utils.logging import log_dist


def device_memory_stats():
    """Aggregate allocator stats over ALL local devices — sum of
    bytes-in-use (what this process holds), max of peak-bytes-in-use
    (the binding per-chip high-water mark; summing peaks would
    overstate a single chip's pressure). device_count=0 means the
    backend exposes no memory_stats (e.g. some CPU runtimes); the
    monitor's memory gauge publishes the same numbers. `host_rss_
    bytes` (from /proc/self/statm, stdlib-only) rides along so the
    gauge and the memory ledger's reconciliation stay meaningful
    off-TPU, where the host RSS IS the run's memory signal."""
    in_use, peak, count = 0, 0, 0
    try:
        import jax
        for dev in jax.local_devices():
            stats = dev.memory_stats() or {}
            if not stats:
                continue
            in_use += int(stats.get("bytes_in_use", 0))
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
            count += 1
    except Exception:  # ds-lint: allow[BROADEXC] allocator stats are optional (absent off-TPU / older jaxlib); gauges degrade to zero
        pass
    out = {"in_use_bytes": in_use, "peak_bytes": peak,
           "device_count": count}
    from deepspeed_tpu.monitor.memory import host_rss_bytes
    rss = host_rss_bytes()
    if rss is not None:
        out["host_rss_bytes"] = rss
    return out


def _device_sync(outputs):
    """Wait until `outputs` — a pytree holding the device arrays the
    timed work produced — are computed. (`jax.effects_barrier` waits
    for side-effecting computations only; an ordinary jitted step has
    none, so fencing with it times the enqueue.)"""
    import jax
    jax.block_until_ready(outputs)


def _nothing_in_flight():
    return None


class SynchronizedWallClockTimer:
    """Named timers with device-fence on start/stop. `sync_on` is a
    zero-argument callable returning the pytree of device arrays the
    timed work last produced (the engine passes its state and loss);
    the default fences nothing, which is right for host-only work."""

    class Timer:
        def __init__(self, name, sync_on=_nothing_in_flight):
            self.name_ = name
            self.sync_on_ = sync_on
            self.elapsed_ = 0.0
            self.started_ = False
            self.start_time = time.time()

        def start(self):
            assert not self.started_, f"timer {self.name_} has already been started"
            _device_sync(self.sync_on_())
            self.start_time = time.time()
            self.started_ = True

        def stop(self, reset=False):
            assert self.started_, "timer is not started"
            _device_sync(self.sync_on_())
            if reset:
                self.elapsed_ = time.time() - self.start_time
            else:
                self.elapsed_ += time.time() - self.start_time
            self.started_ = False

        def reset(self):
            self.elapsed_ = 0.0
            self.started_ = False

        def elapsed(self, reset=True):
            started_ = self.started_
            if self.started_:
                self.stop()
            elapsed_ = self.elapsed_
            if reset:
                self.reset()
            if started_:
                self.start()
            return elapsed_

        def mean(self, reset=True):
            return self.elapsed(reset=reset)

    def __init__(self, sync_on=_nothing_in_flight):
        self.timers = {}
        self.sync_on = sync_on

    def __call__(self, name):
        if name not in self.timers:
            self.timers[name] = self.Timer(name, self.sync_on)
        return self.timers[name]

    def has_timer(self, name):
        return name in self.timers

    @staticmethod
    def memory_usage():
        stats = device_memory_stats()
        if not stats["device_count"]:
            return "DeviceMem=unavailable"
        gib = 1024 ** 3
        return (f"DeviceMemInUse={round(stats['in_use_bytes'] / gib, 2)}"
                f" GB | DevicePeak="
                f"{round(stats['peak_bytes'] / gib, 2)} GB "
                f"(over {stats['device_count']} local devices)")

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False, ranks=None):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed_time = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                string += " | {}: {:.2f}".format(name, elapsed_time)
        log_dist(string, ranks=ranks or [0])


class ThroughputTimer:
    """Samples/sec with warmup-step exclusion (ref `timer.py:97-173`)."""

    def __init__(self,
                 batch_size,
                 num_workers=1,
                 start_step=2,
                 steps_per_output=50,
                 monitor_memory=False,
                 logging_fn=None,
                 sync_on=_nothing_in_flight):
        # zero-argument callable returning the pytree of device arrays
        # the last counted step produced; the window fences wait on it
        self.sync_on = sync_on
        self.start_time = 0
        self.end_time = 0
        self.started = False
        self.batch_size = batch_size or 1
        self.num_workers = num_workers
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn
        if self.logging is None:
            from deepspeed_tpu.utils.logging import logger
            self.logging = logger.info
        self.initialized = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def _init_timer(self):
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True

    def stop(self, report_speed=True, count=1):
        """`count` = microbatches consumed since start() (a fused
        grad-accum step consumes several at once).

        Device fences happen ONLY at measurement-window boundaries (end
        of warmup, and each steps_per_output report) — a per-step fence
        would serialize host and device every step and drain the
        dispatch queue. Between fences the device queue stays full; the
        window's wall time divided by its step count is exact."""
        if not self.started:
            return
        self.started = False
        self.micro_step_count += count
        self.global_step_count += count
        if self.start_time == 0:
            if self.global_step_count >= self.start_step:
                # warmup done: fence once and open the window
                _device_sync(self.sync_on())
                self.start_time = time.time()
                self._steps_at_window_start = self.global_step_count
            return
        if report_speed and \
                self.global_step_count % self.steps_per_output < count:
            _device_sync(self.sync_on())
            self.end_time = time.time()
            window_elapsed = self.end_time - self.start_time
            # cumulative pair: total_elapsed_time / _measured_steps only
            # grow at fences, so avg_samples_per_sec is correct when
            # called mid-window or at end of training (ref ThroughputTimer
            # accumulated total_elapsed_time the same way)
            self.total_elapsed_time += window_elapsed
            self._measured_steps = getattr(self, "_measured_steps", 0) + \
                (self.global_step_count - self._steps_at_window_start)
            self.logging(
                "{}/{}, SamplesPerSec={}".format(
                    self.epoch_count, self.micro_step_count,
                    self.avg_samples_per_sec()))
            # restart the window so a host-side pause (checkpoint save,
            # eval loop) dilutes at most ONE report, not all of them
            self.start_time = self.end_time
            self._steps_at_window_start = self.global_step_count

    def avg_samples_per_sec(self):
        """Cumulative samples/sec over all completed measurement windows
        (post-warmup). Safe to call mid-window — unfenced in-flight steps
        are simply not counted yet; before the first fenced window it
        returns 0.0 (not -inf: callers feed this into logs/ratios).

        Units: `_measured_steps` counts MICROBATCHES (`stop(count=...)`),
        and one microbatch consumes `batch_size` (micro-batch per
        worker) × `num_workers` samples globally — so gas>1 fused steps
        (count=gas) and dp>1 both cancel out to
        train_batch_size × optimizer-steps / elapsed."""
        measured = getattr(self, "_measured_steps", 0)
        if measured > 0 and self.total_elapsed_time > 0:
            samples_per_step = self.batch_size * self.num_workers
            avg_time_per_step = self.total_elapsed_time / measured
            return samples_per_step / avg_time_per_step
        return 0.0
