"""Unified telemetry tests (ISSUE 5).

Covers:
  * JSONL sink — schema version, atomic whole-line appends (including
    from concurrent threads), parseability;
  * native tfevents sink — file readable without torch/tensorflow,
    CRC-verified, scalars round-trip; `get_summary_writer` serves the
    native writer;
  * fence alignment — with monitor enabled and async dispatch on, the
    hot loop performs ZERO per-step `device_get`/`effects_barrier`
    calls, and a fenced window pays exactly ONE device_get per fence
    (the PR 2 guard, extended);
  * the stall watchdog — fires on an artificially stalled loop, stays
    silent on a healthy one;
  * snapshot() — stable key set across bf16 / fp16 / ZeRO-2 / offload
    engines;
  * a 10-step ZeRO-2(+offload wire) run producing a parseable event
    log with loss, lr, loss_scale, throughput, memory, wire bytes and
    checkpoint-commit events;
  * SynchronizedWallClockTimer.memory_usage aggregation across local
    devices;
  * wall_clock_breakdown riding the fence-aligned span path (no
    per-microstep effects_barrier).
"""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

import jax

import deepspeed_tpu
from simple_model import SimpleModel
from deepspeed_tpu.monitor import Monitor, SCHEMA_VERSION
from deepspeed_tpu.monitor.config import (DeepSpeedMonitorConfig,
                                          MonitorConfigError)
from deepspeed_tpu.monitor.registry import MetricsRegistry
from deepspeed_tpu.monitor.sinks import JsonlSink
from deepspeed_tpu.monitor.tfevents import (TFEventsWriter, crc32c,
                                            read_tfevents)
from deepspeed_tpu.monitor.watchdog import StallWatchdog


# ----------------------------------------------------------------------
# helpers
# ----------------------------------------------------------------------
def _make_stacked(seed, bs=16, dim=8, bad=False):
    rng = np.random.RandomState(seed)
    x = rng.randn(bs, dim).astype(np.float32)
    if bad:
        x = np.full((bs, dim), 1e30, np.float32)
    w = np.linspace(-1, 1, dim * dim).reshape(dim, dim).astype(np.float32)
    return {"x": x[None], "y": (x @ w)[None]}


def _engine(config_over=None, monitor=None):
    model = SimpleModel(hidden_dim=8)
    cfg = {
        "train_batch_size": 16,
        "steps_per_print": 10000,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    }
    cfg.update(config_over or {})
    if monitor is not None:
        cfg["monitor"] = monitor
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.params, config=cfg)
    return engine


# ----------------------------------------------------------------------
# JSONL sink
# ----------------------------------------------------------------------
def test_jsonl_sink_schema_and_parse(tmp_path):
    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path)
    sink.emit({"v": SCHEMA_VERSION, "kind": "metrics", "step": 1,
               "loss": 0.5})
    sink.emit({"v": SCHEMA_VERSION, "kind": "ckpt_commit", "step": 2,
               "tag": "t"})
    sink.close()
    lines = open(path).read().splitlines()
    assert len(lines) == 2
    events = [json.loads(l) for l in lines]
    assert all(e["v"] == SCHEMA_VERSION for e in events)
    assert events[0]["kind"] == "metrics"
    assert events[1]["tag"] == "t"


def test_jsonl_sink_concurrent_appends_stay_whole_lines(tmp_path):
    """The atomic-append contract: events emitted from many threads
    (checkpoint writer, watchdog) interleave as whole lines."""
    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path)
    n_threads, per_thread = 8, 50

    def worker(tid):
        for i in range(per_thread):
            sink.emit({"v": 1, "kind": "metrics", "step": i, "tid": tid,
                       "pad": "x" * 200})

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sink.close()
    events = [json.loads(l) for l in open(path)]   # every line parses
    assert len(events) == n_threads * per_thread
    from collections import Counter
    counts = Counter(e["tid"] for e in events)
    assert all(counts[t] == per_thread for t in range(n_threads))


def test_jsonl_sink_appends_across_instances(tmp_path):
    path = str(tmp_path / "events.jsonl")
    for i in range(2):
        sink = JsonlSink(path)
        sink.emit({"v": 1, "kind": "metrics", "step": i})
        sink.close()
    assert [json.loads(l)["step"] for l in open(path)] == [0, 1]


# ----------------------------------------------------------------------
# native tfevents
# ----------------------------------------------------------------------
def test_crc32c_known_vectors():
    # RFC 3720 test vector: 32 zero bytes -> 0x8A9136AA
    assert crc32c(b"\x00" * 32) == 0x8A9136AA
    assert crc32c(b"123456789") == 0xE3069283


def test_tfevents_roundtrip_without_torch(tmp_path):
    w = TFEventsWriter(str(tmp_path))
    w.add_scalar("Train/loss", 1.5, step=3, wall_time=123.0)
    w.add_scalars({"a": 1.0, "b": 2.0}, step=4)
    w.close()
    events = read_tfevents(w.path)
    assert events[0]["file_version"] == "brain.Event:2"
    assert events[1]["step"] == 3
    assert events[1]["wall_time"] == 123.0
    assert events[1]["scalars"] == {"Train/loss": 1.5}
    assert events[2]["step"] == 4
    assert events[2]["scalars"] == {"a": 1.0, "b": 2.0}


def test_tfevents_reader_detects_corruption(tmp_path):
    w = TFEventsWriter(str(tmp_path))
    w.add_scalar("x", 1.0, step=1)
    w.close()
    blob = bytearray(open(w.path, "rb").read())
    blob[-6] ^= 0xFF   # flip a byte inside the last record body
    open(w.path, "wb").write(bytes(blob))
    with pytest.raises(ValueError, match="corrupt"):
        read_tfevents(w.path)


def test_get_summary_writer_is_native(tmp_path, monkeypatch):
    """The legacy tensorboard config block routes through the native
    writer — importing torch anywhere on this path is a regression."""
    import builtins
    real_import = builtins.__import__

    def no_torch(name, *a, **kw):
        if name == "torch" or name.startswith("torch."):
            raise ImportError("torch is not installed")
        return real_import(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", no_torch)
    engine = _engine({
        "tensorboard": {"enabled": True,
                        "output_path": str(tmp_path / "tb"),
                        "job_name": "job"}})
    assert engine.summary_writer is not None
    engine.summary_writer.add_scalar("t", 2.0, 1)
    engine.summary_writer.close()
    files = glob.glob(str(tmp_path / "tb" / "job" /
                          "events.out.tfevents.*"))
    assert files
    events = read_tfevents(files[0])
    assert events[1]["scalars"] == {"t": 2.0}


def test_summary_writer_fallback_warns_and_returns_none(tmp_path):
    engine = _engine()
    # unusable log dir (a file where the dir should be)
    blocker = tmp_path / "blocked"
    blocker.write_text("not a dir")
    engine._config.tensorboard_output_path = str(blocker)
    assert engine.get_summary_writer() is None


# ----------------------------------------------------------------------
# config block
# ----------------------------------------------------------------------
def test_monitor_config_defaults_and_validation():
    cfg = DeepSpeedMonitorConfig({})
    assert cfg.enabled is False
    assert list(cfg.sinks) == ["jsonl"]
    assert cfg.stall_timeout_sec == 0
    with pytest.raises(MonitorConfigError):
        DeepSpeedMonitorConfig({"monitor": {"sinks": ["nope"]}})
    with pytest.raises(MonitorConfigError):
        DeepSpeedMonitorConfig({"monitor": {"stall_timeout_sec": -1}})
    with pytest.raises(MonitorConfigError):
        DeepSpeedMonitorConfig({"monitor": {"flush_interval": -2}})
    cfg = DeepSpeedMonitorConfig(
        {"monitor": {"enabled": True,
                     "sinks": [{"type": "tensorboard"}, "jsonl"],
                     "stall_timeout_sec": 5}})
    assert cfg.enabled and cfg.stall_timeout_sec == 5


# ----------------------------------------------------------------------
# fence alignment (the PR 2 guard, extended for the monitor)
# ----------------------------------------------------------------------
class _SyncCounters:
    def __init__(self, monkeypatch):
        self.device_get = 0
        self.effects_barrier = 0
        real_get, real_barrier = jax.device_get, jax.effects_barrier

        def counting_get(x):
            self.device_get += 1
            return real_get(x)

        def counting_barrier():
            self.effects_barrier += 1
            return real_barrier()

        monkeypatch.setattr(jax, "device_get", counting_get)
        monkeypatch.setattr(jax, "effects_barrier", counting_barrier)


def _guard_engine(tmp_path, mode="bf16", steps_per_sync=10000,
                  wall_clock=False):
    cfg = {
        "train_batch_size": 16,
        "steps_per_print": 10000,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_max_lr": 1e-3,
                                 "warmup_num_steps": 10}},
        "async_dispatch": {"enabled": True,
                           "steps_per_sync": steps_per_sync},
        "wall_clock_breakdown": wall_clock,
    }
    cfg["fp16" if mode == "fp16" else "bf16"] = \
        {"enabled": True, "initial_scale_power": 4} \
        if mode == "fp16" else {"enabled": True}
    return _engine(cfg, monitor={"enabled": True, "sinks": ["jsonl"],
                                 "output_path": str(tmp_path)})


@pytest.mark.parametrize("mode", ["bf16", "fp16"])
def test_monitor_hot_path_zero_per_step_syncs(mode, tmp_path,
                                              monkeypatch):
    """monitor.enabled=true + async dispatch: N train_batch steps
    between fences perform ZERO device_get / effects_barrier calls —
    telemetry folds device-side."""
    engine = _guard_engine(tmp_path, mode)
    batches = [engine.stage_batch(_make_stacked(i)) for i in range(8)]
    for b in batches[:3]:
        engine.train_batch(batch=b)
    counters = _SyncCounters(monkeypatch)
    for b in batches[3:]:
        engine.train_batch(batch=b)
    assert counters.device_get == 0, \
        f"{mode}+monitor hot path device_get x{counters.device_get}"
    assert counters.effects_barrier == 0
    engine.monitor.close()


def test_monitor_fence_costs_exactly_one_device_get(tmp_path,
                                                    monkeypatch):
    """A fenced window pays ONE device_get per fence — the drain of
    the retained device metrics — and nothing per step."""
    engine = _guard_engine(tmp_path, "bf16", steps_per_sync=4)
    batches = [engine.stage_batch(_make_stacked(i)) for i in range(16)]
    # warmup past compile AND past the first fences
    for b in batches[:8]:
        engine.train_batch(batch=b)
    assert engine._host_steps == 8   # next fences at 12 and 16
    counters = _SyncCounters(monkeypatch)
    for b in batches[8:]:
        engine.train_batch(batch=b)
    assert counters.device_get == 2, \
        f"expected 1 device_get per fence (2 fences), got " \
        f"{counters.device_get}"
    assert counters.effects_barrier == 0
    # and the fences actually recorded metrics
    log = os.path.join(str(tmp_path), "events.jsonl")
    kinds = [json.loads(l)["kind"] for l in open(log)]
    assert kinds.count("metrics") >= 2
    engine.monitor.close()


def test_wall_clock_breakdown_does_not_barrier_per_step(tmp_path,
                                                        monkeypatch):
    """wall_clock_breakdown=true now rides the fence-aligned span path:
    zero effects_barrier in the hot loop (the legacy timers fenced the
    device twice per microstep)."""
    engine = _guard_engine(tmp_path, "bf16", wall_clock=True)
    batches = [engine.stage_batch(_make_stacked(i)) for i in range(6)]
    for b in batches[:3]:
        engine.train_batch(batch=b)
    counters = _SyncCounters(monkeypatch)
    for b in batches[3:]:
        engine.train_batch(batch=b)
    assert counters.effects_barrier == 0
    assert counters.device_get == 0
    # spans recorded host-side and drain at the fence
    spans = engine.monitor.trace.drain()
    assert "step" in spans and spans["step"]["count"] == 6
    engine.monitor.close()


def test_wall_clock_breakdown_logs_spans_without_monitor():
    """wall_clock_breakdown=true must keep producing breakdown output on
    its own — the monitor block is NOT required (regression: the span
    line only ever fired inside the monitor.enabled branch)."""
    import logging

    class _Collect(logging.Handler):
        def __init__(self):
            super().__init__()
            self.messages = []

        def emit(self, record):
            self.messages.append(record.getMessage())

    engine = _engine({"wall_clock_breakdown": True, "steps_per_print": 2})
    assert not engine.monitor.enabled
    handler = _Collect()
    logging.getLogger("DeepSpeedTPU").addHandler(handler)
    try:
        for i in range(4):
            engine.train_batch(batch=_make_stacked(i))
    finally:
        logging.getLogger("DeepSpeedTPU").removeHandler(handler)
    span_lines = [m for m in handler.messages if "span ms/step" in m]
    assert span_lines, "no span breakdown logged with monitor disabled"
    assert "step" in span_lines[-1]
    engine.monitor.close()


def test_flatten_numeric_keeps_nested_metadata_names():
    """Only TOP-level event metadata (v/ts/step/kind) is excluded from
    the TensorBoard flattening — a nested span named "step" must
    survive (regression: the filter applied at every depth)."""
    from deepspeed_tpu.monitor.sinks import _flatten_numeric
    event = {"v": 1, "ts": 1.0, "step": 10, "kind": "metrics",
             "loss": 2.5,
             "spans": {"forward": {"ms_per": 1.0},
                       "step": {"ms": 4.0, "count": 2, "ms_per": 2.0}}}
    flat = _flatten_numeric(event)
    assert flat["spans/step/ms_per"] == 2.0
    assert flat["spans/forward/ms_per"] == 1.0
    assert flat["loss"] == 2.5
    assert "step" not in flat and "v" not in flat


def test_forward_backward_step_spans_recorded(tmp_path):
    engine = _guard_engine(tmp_path, "bf16", wall_clock=True)
    batch = {"x": np.random.RandomState(0).randn(16, 8).astype(np.float32),
             "y": np.zeros((16, 8), np.float32)}
    loss = engine(batch)
    engine.backward(loss)
    engine.step()
    spans = engine.monitor.trace.drain()
    assert {"forward", "backward", "step"} <= set(spans)
    engine.monitor.close()


# ----------------------------------------------------------------------
# watchdog
# ----------------------------------------------------------------------
def test_watchdog_fires_on_stall_and_not_on_healthy():
    fired = []
    wd = StallWatchdog(timeout_sec=0.3, on_stall=fired.append,
                       poll_interval=0.05)
    try:
        wd.arm()
        # healthy: fences keep arriving inside the timeout
        for _ in range(4):
            time.sleep(0.1)
            wd.notify_fence()
        assert not fired and wd.stall_count == 0
        # stall: no fence for > timeout
        wd.heartbeat("prefetch")
        deadline = time.time() + 3.0
        while not fired and time.time() < deadline:
            time.sleep(0.05)
        assert fired, "watchdog did not fire on a stalled loop"
        diag = fired[0]
        assert diag["fence_age_sec"] >= 0.3
        assert "prefetch" in diag["heartbeat_age_sec"]
        # one episode fires once, then re-arms on the next fence
        n = len(fired)
        time.sleep(0.5)
        assert len(fired) == n
        wd.notify_fence()
        assert wd.stall_count == 1
    finally:
        wd.stop()


def test_watchdog_engine_wiring_stalled_vs_healthy(tmp_path):
    """End-to-end: a training loop that stops stepping trips the
    watchdog; one that keeps fencing does not."""
    engine = _engine(
        {"async_dispatch": {"enabled": True, "steps_per_sync": 1},
         "bf16": {"enabled": True}},
        monitor={"enabled": True, "sinks": ["jsonl"],
                 "output_path": str(tmp_path),
                 "stall_timeout_sec": 0.4})
    engine.monitor.watchdog._poll = 0.05   # fast polling for the test
    fired = []
    engine.monitor.watchdog.on_stall = fired.append
    for i in range(6):
        engine.train_batch(batch=_make_stacked(i))
    assert not fired, "healthy loop tripped the watchdog"
    time.sleep(1.0)     # artificial stall: loop stops stepping
    assert fired, "stalled loop did not trip the watchdog"
    # the stall event also landed in the sink
    log = os.path.join(str(tmp_path), "events.jsonl")
    kinds = [json.loads(l)["kind"] for l in open(log)]
    assert "stall" in kinds
    engine.monitor.close()


def test_monitor_disabled_creates_no_watchdog_or_sinks(tmp_path):
    engine = _engine({"bf16": {"enabled": True}})
    assert engine.monitor.enabled is False
    assert engine.monitor.watchdog is None
    assert engine.monitor.sinks == []
    engine.train_batch(batch=_make_stacked(0))
    assert engine.monitor.on_fence() is None
    # snapshot still answers with the stable schema
    snap = engine.monitor.snapshot()
    assert set(snap) == set(Monitor.SNAPSHOT_KEYS)


def test_an_attached_sink_is_handed_host_events_with_the_monitor_off():
    """`attach_sink`: a caller's own sink gets the host events
    (`event`) whether or not the config enabled the monitor; nothing
    else is switched on for it, and the fence emits nothing."""
    engine = _engine({"bf16": {"enabled": True}})
    seen = []

    class Sink:
        emit = staticmethod(seen.append)

    engine.monitor.event("decode_batch", iterations=4)      # nobody hears
    engine.monitor.attach_sink(Sink())
    engine.monitor.event("decode_batch", iterations=4, loop_s=0.5)
    assert [(e["kind"], e["iterations"], e["loop_s"]) for e in seen] == \
        [("decode_batch", 4, 0.5)]
    assert {"v", "ts", "step"} <= set(seen[0])
    assert engine.monitor.enabled is False
    assert engine.monitor.watchdog is None and engine.monitor.flight is None
    engine.train_batch(batch=_make_stacked(0))
    assert engine.monitor.on_fence() is None and len(seen) == 1


# ----------------------------------------------------------------------
# snapshot schema stability
# ----------------------------------------------------------------------
_SNAP_CONFIGS = {
    "bf16": {"bf16": {"enabled": True}},
    "fp16": {"fp16": {"enabled": True, "initial_scale_power": 4}},
    "zero2": {"bf16": {"enabled": True},
              "zero_optimization": {"stage": 2}},
    "offload": {"bf16": {"enabled": True},
                "zero_optimization": {
                    "stage": 2, "cpu_offload": True,
                    "offload_wire": {"grad_bits": 8, "param_bits": 8}}},
}


@pytest.mark.parametrize("name", sorted(_SNAP_CONFIGS))
def test_snapshot_keys_stable_across_engines(name, tmp_path):
    engine = _engine(_SNAP_CONFIGS[name],
                     monitor={"enabled": True, "sinks": [],
                              "output_path": str(tmp_path)})
    for i in range(3):
        engine.train_batch(batch=_make_stacked(i))
    snap = engine.monitor.snapshot()
    assert set(snap) == set(Monitor.SNAPSHOT_KEYS)
    assert snap["schema"] == SCHEMA_VERSION
    assert snap["step"] == 3
    assert np.isfinite(snap["loss"])
    assert snap["lr"] is not None
    assert set(snap["wire"]) == {"d2h_bytes", "h2d_bytes", "grad_bits",
                                 "param_bits"}
    assert set(snap["checkpoint"]) == {"queue_depth", "commits",
                                       "last_commit_ms"}
    assert set(snap["prefetch"]) == {"occupancy", "depth"}
    if name == "offload":
        assert snap["wire"]["d2h_bytes"] > 0
        assert snap["wire"]["grad_bits"] == 8
    else:
        assert snap["wire"]["d2h_bytes"] == 0
    engine.monitor.close()


# ----------------------------------------------------------------------
# the acceptance run: 10-step ZeRO-2 with the JSONL sink
# ----------------------------------------------------------------------
def test_ten_step_zero2_event_log(tmp_path):
    """10 ZeRO-2(+offload-wire) steps with a checkpoint save produce a
    parseable event log containing loss, lr, loss_scale, throughput,
    memory, wire bytes, and a checkpoint-commit event."""
    engine = _engine(
        {"bf16": {"enabled": True},
         "steps_per_print": 5,
         "zero_optimization": {"stage": 2, "cpu_offload": True,
                               "offload_wire": {"grad_bits": 8,
                                                "param_bits": 8}}},
        monitor={"enabled": True, "sinks": ["jsonl", "tensorboard"],
                 "output_path": str(tmp_path)})
    micro = [{k: v[0] for k, v in _make_stacked(i).items()}
             for i in range(10)]
    loader = engine.prefetch(iter(micro))
    for i in range(10):
        engine.train_batch(data_iter=loader)
    engine.save_checkpoint(str(tmp_path / "ckpt"))
    engine.wait_for_checkpoint()
    engine.monitor.on_fence()     # final drain for the tail steps
    engine.monitor.close()
    loader.close()

    log = os.path.join(str(tmp_path), "events.jsonl")
    events = [json.loads(l) for l in open(log)]
    assert all(e["v"] == SCHEMA_VERSION for e in events)
    metrics = [e for e in events if e["kind"] == "metrics"]
    assert metrics, events
    for e in metrics:
        for key in ("loss", "lr", "loss_scale", "samples_per_sec",
                    "memory", "wire", "checkpoint", "prefetch"):
            assert key in e, (key, e)
        assert np.isfinite(e["loss"])
    assert any(e["wire"]["d2h_bytes"] > 0 for e in metrics)
    commits = [e for e in events if e["kind"] == "ckpt_commit"]
    assert commits and commits[0]["wall_ms"] > 0
    assert commits[0]["tag"].startswith("global_step")

    # the tensorboard sink wrote a loadable (torch-free) file
    tb = glob.glob(os.path.join(str(tmp_path), "tb",
                                "events.out.tfevents.*"))
    assert tb
    tb_events = read_tfevents(tb[0])
    tags = set()
    for e in tb_events:
        tags |= set(e["scalars"])
    assert "monitor/metrics/loss" in tags
    assert "monitor/metrics/wire/d2h_bytes" in tags


# ----------------------------------------------------------------------
# registry unit behavior
# ----------------------------------------------------------------------
def test_registry_compaction_bounds_retention():
    reg = MetricsRegistry()
    reg._COMPACT_AT = 8
    for i in range(30):
        reg.fold_step(loss=float(i), grad_norm=1.0, loss_scale=2.0,
                      overflow=(i % 10 == 0), tokens=100)
    assert len(reg._pending) < 8
    out = reg.drain_device()
    assert out["steps"] == 30
    np.testing.assert_allclose(out["loss"], np.mean(np.arange(30.0)))
    assert out["overflow_count"] == 3
    assert out["tokens"] == 3000
    assert out["loss_scale"] == 2.0
    assert reg.drain_device() is None


def test_registry_counters_and_gauges():
    reg = MetricsRegistry()
    reg.inc("c", 2.0)
    reg.inc("c")
    reg.set_counter("d", 7.0)
    assert reg.counters() == {"c": 3.0, "d": 7.0}
    reg.add_gauge("g", lambda: 1.5)
    reg.add_gauge("h", lambda: {"a": 1.0})
    reg.add_gauge("boom", lambda: 1 / 0)   # failures are swallowed
    assert reg.sample_gauges() == {"g": 1.5, "h/a": 1.0}


# ----------------------------------------------------------------------
# memory aggregation satellite
# ----------------------------------------------------------------------
def test_memory_usage_aggregates_local_devices(monkeypatch):
    from deepspeed_tpu.utils import timer as timer_mod

    class FakeDev:
        def __init__(self, in_use, peak):
            self._s = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}

        def memory_stats(self):
            return self._s

    gib = 1024 ** 3
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [FakeDev(1 * gib, 2 * gib),
                                 FakeDev(3 * gib, 5 * gib)])
    stats = timer_mod.device_memory_stats()
    assert stats["in_use_bytes"] == 4 * gib     # sum across devices
    assert stats["peak_bytes"] == 5 * gib       # max across devices
    assert stats["device_count"] == 2
    text = timer_mod.SynchronizedWallClockTimer.memory_usage()
    assert "4.0 GB" in text and "5.0 GB" in text and "2 local" in text


def test_ds_report_smoke(capsys):
    from deepspeed_tpu import env_report
    env_report.main()
    out = capsys.readouterr().out
    assert "monitor sinks" in out
    assert "jax version" in out
    assert "Pallas flash attention" in out


def test_snapshot_mfu_and_tokens_per_sec(tmp_path):
    """ISSUE 6 satellite: once the throughput timer has a warmed
    measurement window, snapshot() (and the fence metrics event) carry
    the bench-computed tokens_per_sec_per_chip — and mfu on TPU (None
    on CPU, where no nominal peak applies).  Pre-warmup both keys are
    present with None (schema stability, not missing keys)."""
    engine = _engine({"steps_per_print": 4},
                     monitor={"enabled": True, "sinks": [],
                              "output_path": str(tmp_path)})
    snap0 = engine.monitor.snapshot()
    assert set(snap0) == set(Monitor.SNAPSHOT_KEYS)
    assert snap0["tokens_per_sec_per_chip"] is None
    assert snap0["mfu"] is None
    # steps_per_print=4 -> the tput window fences after ~4 microsteps
    for i in range(10):
        engine.train_batch(batch=_make_stacked(i))
    snap = engine.monitor.snapshot()
    assert snap["tokens_per_sec_per_chip"] is not None
    assert snap["tokens_per_sec_per_chip"] > 0
    import jax
    if jax.devices()[0].platform != "tpu":
        assert snap["mfu"] is None   # no nominal CPU peak to divide by
    # the fence event shares the derived keys
    event = engine.monitor.on_fence()
    if event is not None:
        assert "tokens_per_sec_per_chip" in event and "mfu" in event
