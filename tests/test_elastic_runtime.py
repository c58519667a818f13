"""Elastic preemption-safe runtime tests (ISSUE 10).

What these pin:
  * the failure taxonomy (`classify_failure`) and runtime-config
    validation;
  * `FaultInjector` sentinel lifecycle: a SIGKILL'd "host" subprocess
    surfaces as exactly one `host_lost` event;
  * `engine.wait_for_checkpoint(timeout=...)` raises a
    `CheckpointWaitTimeout` (with the writer's heartbeat age) instead
    of deadlocking on a hung writer, and `abandon_checkpoint_writers`
    detaches it;
  * checkpoint load retry/backoff and the distinct
    staging-only-vs-nothing error taxonomy;
  * watchdog escalation: consecutive-fire counting, ONE terminal
    `stall_escalated` per episode, re-arm on fence;
  * the supervisor end-to-end on the virtual mesh: lose a host ->
    re-form on the survivors (re-derived micro-batch, re-planned ZeRO
    bytes strictly smaller per remaining device count), resume from
    the last committed tag with loss continuity asserted; capacity
    returns -> grow at the next checkpoint boundary;
  * the CHAOS test (subprocess — the PR-8/9 isolation precedent):
    SIGKILL a sentinel host mid-step, prove the post-resume loss
    trajectory is BIT-IDENTICAL to a clean engine restarted from the
    same checkpoint on the same surviving mesh, and that a scale-up
    restores the original device count at a checkpoint boundary.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.elasticity import ElasticityConfigError
from deepspeed_tpu.elasticity.runtime import (
    CAPACITY_RETURNED, HOST_LOST, HOST_SLOW, STALL, STALL_ESCALATED,
    BatchSpec, ElasticRuntimeConfig, ElasticSupervisor, FaultEvent,
    FaultInjector, classify_failure)
from deepspeed_tpu.runtime import checkpoint as ckpt_io
from deepspeed_tpu.monitor.watchdog import StallWatchdog
from deepspeed_tpu.utils.compile_cache import compile_cache_dir

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

D, H = 24, 48


def _model_factory():
    rng = np.random.RandomState(0)
    params = {"w1": np.asarray(rng.randn(D, H) * 0.1, np.float32),
              "b1": np.zeros(H, np.float32),
              "w2": np.asarray(rng.randn(H, 1) * 0.1, np.float32)}

    def loss_fn(p, batch, rngs=None, deterministic=False):
        h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)

    return loss_fn, params


def _batch_fn(step, spec):
    rng = np.random.RandomState(1000 + step)
    x = rng.randn(spec.total, D).astype(np.float32)
    y = (x[:, :1] * 0.5).astype(np.float32)
    return {"x": x.reshape(spec.gas, spec.rows, D),
            "y": y.reshape(spec.gas, spec.rows, 1)}


def _ds_config(hosts=4, interval=2, **runtime_over):
    runtime = {"enabled": True, "hosts": hosts,
               "checkpoint_interval": interval,
               "drain_timeout_sec": 5.0, "escalate_after": 2}
    runtime.update(runtime_over)
    return {
        "steps_per_print": 10000,
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "elasticity": {"enabled": True, "max_train_batch_size": 48,
                       "micro_batch_sizes": [2], "version": 0.1,
                       "runtime": runtime},
    }


# ----------------------------------------------------------------------
# failure taxonomy + runtime config
# ----------------------------------------------------------------------
def test_classify_failure_taxonomy():
    # lost dominates the verdict, but a straggler reported in the same
    # batch is dropped too (events are one-shot)
    kind, hosts, ret, n = classify_failure(
        [FaultEvent(HOST_SLOW, host=1), FaultEvent(HOST_LOST, host=2),
         FaultEvent(STALL)], 0, 3)
    assert (kind, hosts, ret, n) == (HOST_LOST, [1, 2], [], 0)
    # slow host is a verdict on its own
    kind, hosts, _, _ = classify_failure(
        [FaultEvent(HOST_SLOW, host=0)], 0, 3)
    assert (kind, hosts) == (HOST_SLOW, [0])
    # transient stalls accumulate, then escalate at the threshold
    kind, _, _, n = classify_failure([FaultEvent(STALL)], 0, 3)
    assert (kind, n) == (STALL, 1)
    kind, _, _, n = classify_failure([FaultEvent(STALL)], 2, 3)
    assert (kind, n) == (STALL_ESCALATED, 0)
    # an explicit watchdog escalation is terminal immediately
    kind, _, _, _ = classify_failure([FaultEvent(STALL_ESCALATED)], 0, 3)
    assert kind == STALL_ESCALATED
    # capacity return rides along with a healthy poll
    kind, _, ret, _ = classify_failure(
        [FaultEvent(CAPACITY_RETURNED, host=3)], 0, 3)
    assert kind is None and ret == [3]


def test_elastic_runtime_config_validation():
    assert not ElasticRuntimeConfig({}).enabled
    cfg = ElasticRuntimeConfig({"enabled": True, "hosts": 4})
    assert cfg.enabled and cfg.hosts == 4
    for bad in ({"hosts": 0}, {"checkpoint_interval": 0},
                {"drain_timeout_sec": 0}, {"load_retries": -1},
                {"max_recoveries": 0}):
        with pytest.raises(ElasticityConfigError):
            ElasticRuntimeConfig(dict({"enabled": True}, **bad))


def test_supervisor_requires_enabled_blocks():
    with pytest.raises(ElasticityConfigError):
        ElasticSupervisor({}, _model_factory, _batch_fn)
    cfg = _ds_config()
    cfg["elasticity"]["runtime"]["enabled"] = False
    with pytest.raises(ElasticityConfigError):
        ElasticSupervisor(cfg, _model_factory, _batch_fn)


def test_supervisor_rejects_model_parallel_mesh():
    """The supervisor re-forms pure data-parallel meshes; a tensor- or
    pipe-parallel mesh config must fail loudly, not silently degrade
    to dp-only."""
    cfg = _ds_config()
    cfg["mesh"] = {"model": 2}
    with pytest.raises(ElasticityConfigError, match="mesh.model"):
        ElasticSupervisor(cfg, _model_factory, _batch_fn)


def test_abandoned_writer_guard_survives_rebuild(tmp_path):
    """The same-tag staging guard must survive the engine rebuild a
    recovery performs: a stale abandoned writer still holding
    global_step2's staging dir blocks the REBUILT engine's replayed
    save of that tag (the next boundary's tag is free)."""

    class _StuckWriter:
        def pending(self):
            return 1

        def tag_in_flight(self, tag):
            return tag == "global_step2"

    inj = FaultInjector()
    sup = ElasticSupervisor(_ds_config(), _model_factory, _batch_fn,
                            save_dir=str(tmp_path / "ckpt"),
                            injector=inj)
    try:
        sup.run(1)
        sup.engine._abandoned_ckpt_writers = [_StuckWriter()]
        inj.mark_host_lost(3)
        sup.run(4)
        save = tmp_path / "ckpt"
        assert not (save / "global_step2").exists(), \
            "rebuilt engine wrote into a staging dir a stale writer owns"
        assert (save / "global_step4").exists()
        assert ckpt_io.read_latest_tag(str(save)) == "global_step4"
    finally:
        sup.close()


def test_batch_spec_rows():
    assert BatchSpec(world=6, micro=2, gas=4, total=48).rows == 12


# ----------------------------------------------------------------------
# fault injector sentinels
# ----------------------------------------------------------------------
def test_fault_injector_sentinel_sigkill_reports_once():
    with FaultInjector() as inj:
        pid = inj.spawn_host(0)
        inj.spawn_host(1)
        assert inj.poll() == []
        inj.sigkill_host(0)
        deadline = time.time() + 5.0
        events = []
        while not events and time.time() < deadline:
            events = inj.poll()
            time.sleep(0.01)
        assert [e.kind for e in events] == [HOST_LOST]
        assert events[0].host == 0 and events[0].info["pid"] == pid
        # reported exactly once; the surviving sentinel stays quiet
        assert inj.poll() == []
    # close() reaped the survivor
    assert inj.poll() == []


def test_fault_injector_respawn_after_death():
    """capacity_returned hosts get re-backed: a dead sentinel is
    evicted on respawn (and the new sentinel's death reports again);
    respawning over a LIVE sentinel is an error."""
    with FaultInjector() as inj:
        inj.spawn_host(0)
        with pytest.raises(ValueError, match="live sentinel"):
            inj.spawn_host(0)
        inj.sigkill_host(0)
        assert inj.wait_host_dead(0)
        deadline = time.time() + 5.0
        while not inj.poll() and time.time() < deadline:
            time.sleep(0.01)
        pid2 = inj.spawn_host(0)
        assert pid2 and not inj.host_dead(0)
        inj.sigkill_host(0)
        assert inj.wait_host_dead(0)
        events = []
        deadline = time.time() + 5.0
        while not events and time.time() < deadline:
            events = inj.poll()
            time.sleep(0.01)
        assert [e.kind for e in events] == [HOST_LOST]


def test_fault_injector_direct_events():
    inj = FaultInjector()
    inj.mark_host_lost(2, reason="preempted")
    inj.mark_host_slow(1)
    inj.inject_stall()
    inj.return_capacity(2)
    kinds = [e.kind for e in inj.poll()]
    assert kinds == [HOST_LOST, HOST_SLOW, STALL, CAPACITY_RETURNED]
    assert inj.poll() == []


# ----------------------------------------------------------------------
# wait_for_checkpoint timeout + abandon (satellite 1)
# ----------------------------------------------------------------------
def _tiny_engine(tmp_path, mesh_devices=8):
    from deepspeed_tpu import initialize
    from deepspeed_tpu.runtime.mesh import build_mesh
    model, params = _model_factory()
    mesh = build_mesh({"pipe": 1, "data": mesh_devices, "model": 1})
    engine, _, _, _ = initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 3,
                "train_batch_size": 2 * 3 * mesh_devices,
                "steps_per_print": 10000,
                "zero_optimization": {"stage": 2},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}},
        mesh=mesh)
    return engine


def test_wait_for_checkpoint_timeout_raises_and_abandon(tmp_path):
    engine = _tiny_engine(tmp_path)
    spec = BatchSpec(world=8, micro=2, gas=3, total=48)
    engine.train_batch(batch=_batch_fn(0, spec))

    real_write = engine._write_checkpoint
    release = {"t": 0.6}

    def slow_write(*a, **kw):
        time.sleep(release["t"])
        return real_write(*a, **kw)

    engine._write_checkpoint = slow_write
    assert engine.save_checkpoint(str(tmp_path), tag="slow",
                                  async_save=True)
    with pytest.raises(ckpt_io.CheckpointWaitTimeout) as ei:
        engine.wait_for_checkpoint(timeout=0.05)
    assert ei.value.pending == 1
    assert "abandon" in str(ei.value)
    # abandon detaches the writer; the engine can keep saving
    writer = engine._ckpt_writer
    assert engine.abandon_checkpoint_writers() == 1
    assert engine._ckpt_writer is None
    # the abandoned writer still commits its tag dir atomically, but
    # must NOT move `latest` — it may be racing a successor engine
    # that already committed newer tags
    writer.wait()
    assert os.path.isdir(tmp_path / "slow")
    assert ckpt_io.read_latest_tag(str(tmp_path)) is None
    # a post-abandon save gets a fresh writer that owns `latest` again
    assert engine.save_checkpoint(str(tmp_path), tag="fresh",
                                  async_save=True)
    engine.wait_for_checkpoint()
    assert ckpt_io.read_latest_tag(str(tmp_path)) == "fresh"
    engine.shutdown()


def test_abandoned_writer_same_tag_save_skipped(tmp_path):
    """A save must refuse to reuse a tag whose staging dir a live
    ABANDONED writer job may still own (two writers in one `<tag>.tmp`
    would commit a torn checkpoint); once that job ends, the tag is
    free again."""
    engine = _tiny_engine(tmp_path)
    spec = BatchSpec(world=8, micro=2, gas=3, total=48)
    engine.train_batch(batch=_batch_fn(0, spec))
    real_write = engine._write_checkpoint

    def slow_write(*a, **kw):
        time.sleep(0.8)
        return real_write(*a, **kw)

    engine._write_checkpoint = slow_write
    assert engine.save_checkpoint(str(tmp_path), tag="t",
                                  async_save=True)
    with pytest.raises(ckpt_io.CheckpointWaitTimeout):
        engine.wait_for_checkpoint(timeout=0.05)
    writer = engine._ckpt_writer
    engine.abandon_checkpoint_writers()
    assert engine.save_checkpoint(str(tmp_path), tag="t",
                                  async_save=True) is False
    writer.wait()
    engine._write_checkpoint = real_write
    assert engine.save_checkpoint(str(tmp_path), tag="t",
                                  async_save=True)
    engine.wait_for_checkpoint()
    assert ckpt_io.read_latest_tag(str(tmp_path)) == "t"
    engine.shutdown()


def test_shutdown_abandons_hung_writer(tmp_path):
    engine = _tiny_engine(tmp_path)
    spec = BatchSpec(world=8, micro=2, gas=3, total=48)
    engine.train_batch(batch=_batch_fn(0, spec))
    real_write = engine._write_checkpoint

    def slow_write(*a, **kw):
        time.sleep(2.0)
        return real_write(*a, **kw)

    engine._write_checkpoint = slow_write
    engine.save_checkpoint(str(tmp_path), tag="hung", async_save=True)
    writer = engine._ckpt_writer
    t0 = time.monotonic()
    engine.shutdown(checkpoint_timeout=0.05)
    assert time.monotonic() - t0 < 1.5, "shutdown blocked on the writer"
    assert engine._ckpt_writer is None
    writer.wait()   # drain so the test leaves no stray thread


# ----------------------------------------------------------------------
# load retry/backoff + error taxonomy (satellite 2)
# ----------------------------------------------------------------------
def test_checkpoint_not_found_vs_staging_only(tmp_path):
    # nothing at all -> CheckpointNotFoundError, never retried (a
    # checkpoint that was never saved cannot appear by waiting)
    t0 = time.monotonic()
    with pytest.raises(ckpt_io.CheckpointNotFoundError):
        ckpt_io.load_checkpoint_flat(str(tmp_path), "never",
                                     retries=5, backoff_sec=0.2)
    assert time.monotonic() - t0 < 0.5
    # tag dir present but manifest missing (mp_rank mismatch /
    # corruption) -> also terminal NotFound, not a burned retry loop
    os.makedirs(tmp_path / "nomanifest")
    t0 = time.monotonic()
    with pytest.raises(ckpt_io.CheckpointNotFoundError,
                       match="manifest"):
        ckpt_io.load_checkpoint_flat(str(tmp_path), "nomanifest",
                                     retries=5, backoff_sec=0.2)
    assert time.monotonic() - t0 < 0.5
    # staging-only (interrupted save) -> distinct actionable error;
    # IS retried (a same-tag resave's two-rename commit window shows
    # the same signature transiently) but stays terminal once the
    # bounded retries exhaust
    os.makedirs(tmp_path / "broken.tmp")
    with pytest.raises(ckpt_io.CheckpointStagingOnlyError) as ei:
        ckpt_io.load_checkpoint_flat(str(tmp_path), "broken")
    assert "interrupted save" in str(ei.value)
    with pytest.raises(ckpt_io.CheckpointStagingOnlyError):
        ckpt_io.load_checkpoint_flat(str(tmp_path), "broken",
                                     retries=2, backoff_sec=0.01)
    # both are FileNotFoundError subclasses (back-compat)
    assert issubclass(ckpt_io.CheckpointNotFoundError, FileNotFoundError)
    assert issubclass(ckpt_io.CheckpointStagingOnlyError,
                      FileNotFoundError)


def test_retry_read_bounded_backoff():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert ckpt_io._retry_read(flaky, retries=3, backoff_sec=0.01,
                               describe="test") == "ok"
    assert calls["n"] == 3
    calls["n"] = 0
    with pytest.raises(OSError):
        ckpt_io._retry_read(flaky, retries=1, backoff_sec=0.01,
                            describe="test")


def test_read_latest_tag_retries(tmp_path, monkeypatch):
    ckpt_io.write_latest_tag(str(tmp_path), "tagA")
    real_open = open
    fails = {"n": 1}

    def flaky_open(path, *a, **kw):
        if str(path).endswith("latest") and fails["n"] > 0 and \
                "r" in (a[0] if a else kw.get("mode", "r")):
            fails["n"] -= 1
            raise OSError("transient NFS flutter")
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", flaky_open)
    assert ckpt_io.read_latest_tag(str(tmp_path), retries=2,
                                   backoff_sec=0.01) == "tagA"


# ----------------------------------------------------------------------
# watchdog escalation (satellite 3)
# ----------------------------------------------------------------------
def test_watchdog_escalates_exactly_once_per_episode():
    fired, escalated, emitted = [], [], []
    wd = StallWatchdog(timeout_sec=0.15, on_stall=fired.append,
                       poll_interval=0.03, escalate_after=2,
                       on_escalate=escalated.append,
                       emit=lambda kind, d: emitted.append(kind))
    try:
        wd.arm()
        deadline = time.time() + 5.0
        while len(escalated) < 1 and time.time() < deadline:
            time.sleep(0.02)
        assert len(escalated) == 1, "no escalation"
        assert escalated[0]["consecutive_fires"] == 2
        assert escalated[0]["escalate_after"] == 2
        assert wd.stall_count >= 2
        # terminal: the episode goes quiet after escalating
        n_fired, n_esc = len(fired), wd.escalation_count
        time.sleep(0.5)
        assert len(fired) == n_fired and wd.escalation_count == n_esc
        assert emitted.count("stall_escalated") == 1
        # a fence re-arms: the next episode escalates again
        wd.notify_fence()
        deadline = time.time() + 5.0
        while wd.escalation_count < 2 and time.time() < deadline:
            time.sleep(0.02)
        assert wd.escalation_count == 2
        assert emitted.count("stall_escalated") == 2
    finally:
        wd.stop()


def test_watchdog_default_fires_once_per_episode():
    """escalate_after=0 keeps the pre-existing contract: ONE fire per
    stall episode, no terminal event."""
    fired = []
    wd = StallWatchdog(timeout_sec=0.15, on_stall=fired.append,
                       poll_interval=0.03)
    try:
        wd.arm()
        deadline = time.time() + 5.0
        while not fired and time.time() < deadline:
            time.sleep(0.02)
        assert len(fired) == 1
        time.sleep(0.5)
        assert len(fired) == 1 and wd.escalation_count == 0
    finally:
        wd.stop()


def test_monitor_config_escalate_after():
    from deepspeed_tpu.monitor.config import (DeepSpeedMonitorConfig,
                                              MonitorConfigError)
    cfg = DeepSpeedMonitorConfig(
        {"monitor": {"enabled": True, "stall_timeout_sec": 5,
                     "stall_escalate_after": 3}})
    assert cfg.stall_escalate_after == 3
    assert DeepSpeedMonitorConfig({}).stall_escalate_after == 0
    with pytest.raises(MonitorConfigError):
        DeepSpeedMonitorConfig(
            {"monitor": {"stall_escalate_after": -1}})


# ----------------------------------------------------------------------
# supervisor end-to-end on the virtual mesh (in-process)
# ----------------------------------------------------------------------
def test_supervisor_lost_host_shrinks_resumes_and_regrows(tmp_path):
    inj = FaultInjector()
    sup = ElasticSupervisor(_ds_config(), _model_factory, _batch_fn,
                            save_dir=str(tmp_path / "ckpt"),
                            injector=inj)
    try:
        sup.run(3)
        assert sup.batch_spec == BatchSpec(world=8, micro=2, gas=3,
                                           total=48)
        plan8 = dict(sup.zero_plan)
        inj.mark_host_lost(3, reason="preemption")
        sup.run(8)
        # re-formed on the 6 survivors with the re-derived micro-batch
        assert sup.batch_spec == BatchSpec(world=6, micro=2, gas=4,
                                           total=48)
        assert len(sup.devices) == 6
        rec = [e for e in sup.events if e["kind"] == "recovery"][0]
        assert rec["cause"] == HOST_LOST and rec["lost_hosts"] == [3]
        assert rec["resumed_from_tag"] == "global_step2"
        assert rec["resumed_step"] == 2
        assert rec["replayed_steps"] == 1   # lost at step 3, ckpt at 2
        assert rec["detect_to_resume_sec"] < 30
        # the re-planned ZeRO state grows per-device when dp shrinks
        # (same total bytes over fewer devices)
        assert rec["zero_plan_bytes"]["opt_state"] > plan8["opt_state"]
        # loss continuity held across the replayed step (asserted
        # inside _note_loss; reaching here means it passed) and the
        # history is contiguous
        assert sorted(sup.loss_history) == list(range(8))
        # capacity returns -> grow at the NEXT checkpoint boundary
        inj.return_capacity(3)
        sup.run(12)
        assert sup.batch_spec.world == 8 and len(sup.devices) == 8
        up = [e for e in sup.events if e["kind"] == "scale_up"][0]
        assert up["world_before"] == 6 and up["world_after"] == 8
        assert up["resumed_step"] % 2 == 0   # boundary-aligned
        assert all(np.isfinite(v) for v in sup.loss_history.values())
    finally:
        sup.close()


def test_supervisor_slow_host_treated_as_lost(tmp_path):
    inj = FaultInjector()
    sup = ElasticSupervisor(_ds_config(), _model_factory, _batch_fn,
                            save_dir=str(tmp_path / "ckpt"),
                            injector=inj)
    try:
        sup.run(2)
        inj.mark_host_slow(0)
        sup.run(4)
        assert sup.batch_spec.world == 6
        rec = [e for e in sup.events if e["kind"] == "recovery"][0]
        assert rec["cause"] == HOST_SLOW and rec["lost_hosts"] == [0]
    finally:
        sup.close()


def test_supervisor_injected_stalls_escalate_to_inplace_recovery(
        tmp_path):
    inj = FaultInjector()
    sup = ElasticSupervisor(_ds_config(), _model_factory, _batch_fn,
                            save_dir=str(tmp_path / "ckpt"),
                            injector=inj)
    try:
        sup.run(4)
        # one transient stall: no recovery
        inj.inject_stall()
        sup.run(5)
        assert not sup.events
        # the stall vote PERSISTS across polls (slow-but-completing
        # steps must not launder a persistent stall): one more single
        # vote in a later poll reaches escalate_after=2 -> in-place
        # recovery
        inj.inject_stall()
        sup.run(8)
        rec = [e for e in sup.events if e["kind"] == "recovery"][0]
        assert rec["cause"] == STALL_ESCALATED
        assert rec["world_before"] == rec["world_after"] == 8
    finally:
        sup.close()


def test_supervisor_batch_fn_failure_recovers(tmp_path):
    """An input-pipeline exception recovers exactly like an engine
    failure instead of killing the supervised loop."""
    boom = {"at": 3}

    def flaky_batch_fn(step, spec):
        if step == boom["at"]:
            boom["at"] = -1   # only once
            raise OSError("data source hiccup")
        return _batch_fn(step, spec)

    sup = ElasticSupervisor(_ds_config(), _model_factory,
                            flaky_batch_fn,
                            save_dir=str(tmp_path / "ckpt"))
    try:
        sup.run(6)
        rec = [e for e in sup.events if e["kind"] == "recovery"][0]
        assert rec["cause"] == "engine_error"
        assert "hiccup" in rec["error"]
        assert sorted(sup.loss_history) == list(range(6))
    finally:
        sup.close()


def test_supervisor_lost_and_returned_in_one_poll(tmp_path):
    """A host reported lost AND returned in the same poll batch must
    first be dropped (recovery on the survivors) and then rejoin at
    the next checkpoint boundary — not be silently eaten."""
    inj = FaultInjector()
    sup = ElasticSupervisor(_ds_config(), _model_factory, _batch_fn,
                            save_dir=str(tmp_path / "ckpt"),
                            injector=inj)
    try:
        sup.run(3)
        inj.mark_host_lost(2)
        inj.return_capacity(2)
        sup.run(8)
        rec = [e for e in sup.events if e["kind"] == "recovery"][0]
        assert rec["cause"] == HOST_LOST and rec["world_after"] == 6
        ups = [e for e in sup.events if e["kind"] == "scale_up"]
        assert ups and ups[0]["world_after"] == 8
        assert sup.batch_spec.world == 8
    finally:
        sup.close()


def test_grow_deferred_until_boundary_save_commits(tmp_path):
    """A grow is voluntary: when the boundary save fails to commit,
    growing must be DEFERRED (not reload an older tag and discard
    work)."""
    inj = FaultInjector()
    sup = ElasticSupervisor(_ds_config(), _model_factory, _batch_fn,
                            save_dir=str(tmp_path / "ckpt"),
                            injector=inj)
    try:
        sup.run(2)
        inj.mark_host_lost(3)
        sup.run(4)
        assert sup.batch_spec.world == 6
        inj.return_capacity(3)
        # break the boundary save: _checkpoint swallows the error, so
        # latest stays at global_step4 and the grow must defer
        sup.engine.save_checkpoint = \
            lambda *a, **kw: (_ for _ in ()).throw(
                RuntimeError("disk full"))
        sup.run(6)
        assert sup.batch_spec.world == 6, \
            "grew despite an uncommitted boundary save"
        assert sup._pending_grow
        assert not [e for e in sup.events if e["kind"] == "scale_up"]
        # saving works again -> the next boundary grows
        del sup.engine.save_checkpoint
        sup.run(8)
        assert sup.batch_spec.world == 8
        up = [e for e in sup.events if e["kind"] == "scale_up"][0]
        assert up["resumed_from_tag"] == "global_step8"
        # no work was lost across the deferral
        assert sorted(sup.loss_history) == list(range(8))
    finally:
        sup.close()


def test_supervisor_restart_adopts_committed_progress(tmp_path):
    """A supervisor restart (the process-death recovery story) resumes
    from the save_dir's committed latest instead of step 0."""
    save = str(tmp_path / "ckpt")
    sup = ElasticSupervisor(_ds_config(), _model_factory, _batch_fn,
                            save_dir=save)
    sup.run(4)
    sup.close()
    sup2 = ElasticSupervisor(_ds_config(), _model_factory, _batch_fn,
                             save_dir=save)
    try:
        sup2.run(6)
        assert sorted(sup2.loss_history) == [4, 5]
        assert sup2.engine.global_steps == 6
    finally:
        sup2.close()


# ----------------------------------------------------------------------
# THE chaos test (subprocess isolation — the PR-8/9 precedent)
# ----------------------------------------------------------------------
CHAOS_SCRIPT = """
import json, os, sys, threading, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
sys.path.insert(0, {repo!r})
sys.path.insert(0, os.path.join({repo!r}, "tests"))
assert len(jax.devices()) == 8, jax.devices()

from test_elastic_runtime import _batch_fn, _ds_config, _model_factory
from deepspeed_tpu.elasticity.runtime import (ElasticSupervisor,
                                              FaultInjector)
from deepspeed_tpu.runtime.mesh import reform_mesh

save_dir = {save_dir!r}
inj = FaultInjector()
for h in range(4):
    inj.spawn_host(h)

KILL_AT = 2     # SIGKILL mid-step-2: the last committed checkpoint is
END = 8         # global_step2, so the death is detected BEFORE the
                # next boundary and step 2 must be replayed


def batch_fn(step, spec):
    if step == KILL_AT and not inj.host_dead(1):
        # mid-step: the kill lands while this step's batch is being
        # staged/trained, like a real preemption
        threading.Timer(0.0, inj.sigkill_host, args=(1,)).start()
        inj.wait_host_dead(1)   # let the kernel reap the sentinel
    return _batch_fn(step, spec)


sup = ElasticSupervisor(_ds_config(), _model_factory, batch_fn,
                        save_dir=save_dir, injector=inj)
sup.run(END)
rec = [e for e in sup.events if e["kind"] == "recovery"][0]
post = {{s: sup.loss_history[s]
        for s in range(rec["resumed_step"], END)}}
report = sup.report()

# ---- clean restart from the SAME checkpoint on the SAME surviving
# mesh: the bit-identical oracle -------------------------------------
by_id = {{d.id: d for d in jax.devices()}}
devices = [by_id[i] for i in report["device_ids"]]
cfg2 = _ds_config()
cfg2["elasticity"]["runtime"]["hosts"] = 1
sup2 = ElasticSupervisor(cfg2, _model_factory, _batch_fn,
                         save_dir=save_dir, devices=devices)
sup2._build_engine(devices)
sup2.engine.load_checkpoint(save_dir, tag=rec["resumed_from_tag"])
assert int(sup2.engine.global_steps) == rec["resumed_step"]
clean = {{}}
for s in range(rec["resumed_step"], END):
    loss = sup2.engine.train_batch(batch=_batch_fn(s, sup2.batch_spec))
    clean[s] = float(jax.device_get(loss))
sup2.close()

# ---- scale-up: capacity returns, grow at the next boundary ---------
inj.return_capacity(1)
sup.run(END + 4)
grow_world = sup.batch_spec.world
ups = [e for e in sup.events if e["kind"] == "scale_up"]
sup.close()

print(json.dumps({{
    "recovery": rec,
    "post_resume_losses": post,
    "clean_restart_losses": clean,
    "clean_world": sup2.batch_spec.world,
    "grow_world": grow_world,
    "scale_ups": ups,
    "final_losses_finite": all(
        l == l for l in report["losses"].values()),
}}))
"""


MOE_CHAOS_SCRIPT = """
import json, os, sys, threading
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
sys.path.insert(0, {repo!r})
sys.path.insert(0, os.path.join({repo!r}, "tests"))
assert len(jax.devices()) == 8, jax.devices()

from test_elastic_runtime import (_moe_batch_fn, _moe_ds_config,
                                  _moe_model_factory)
from deepspeed_tpu.elasticity.runtime import (ElasticSupervisor,
                                              FaultInjector)

save_dir = {save_dir!r}
inj = FaultInjector()
for h in range(4):
    inj.spawn_host(h)

KILL_AT = 2
END = 6


def batch_fn(step, spec):
    # kill TWO hosts mid-step: the 4 survivors re-form as data=2 x
    # expert=2 (XLA-CPU's emulated collectives are nondeterministically
    # unstable on the odd data=3 submesh a single-host loss would
    # produce under the expert axis — a backend artifact; the recovery
    # semantics under test are identical)
    if step == KILL_AT and not inj.host_dead(1):
        threading.Timer(0.0, inj.sigkill_host, args=(1,)).start()
        threading.Timer(0.0, inj.sigkill_host, args=(2,)).start()
        inj.wait_host_dead(1)
        inj.wait_host_dead(2)
    return _moe_batch_fn(step, spec)


sup = ElasticSupervisor(_moe_ds_config(), _moe_model_factory, batch_fn,
                        save_dir=save_dir, injector=inj)
sup.run(END)
rec = [e for e in sup.events if e["kind"] == "recovery"][0]
post = {{s: sup.loss_history[s]
        for s in range(rec["resumed_step"], END)}}
report = sup.report()
# the re-formed mesh kept the pinned expert axis; data absorbed the loss
mesh_shape = dict(sup.engine.mesh.shape)
moe_active = bool(sup.engine._moe_active)
zero_plan = sup.zero_plan
sup.close()

print(json.dumps({{
    "recovery": rec,
    "post_resume_losses": post,
    "device_ids": report["device_ids"],
    "mesh_shape": mesh_shape,
    "moe_active": moe_active,
    "zero_plan_nonzero": bool(zero_plan and zero_plan.get("params")),
    "spec": {{"world": sup.batch_spec.world,
             "micro": sup.batch_spec.micro,
             "gas": sup.batch_spec.gas,
             "total": sup.batch_spec.total}},
}}))
"""

# the clean-restart oracle runs in its OWN subprocess: a third engine
# build in the chaos process (8-dev supervisor engine -> 6-dev
# recovered engine -> 6-dev oracle engine) trips nondeterministic
# native-memory corruption in XLA-CPU's emulated collectives with the
# 4-axis mesh's all-to-alls — a backend artifact, not recovery
# semantics; the oracle's own process builds exactly one engine, the
# shape every manual repro of it is stable in
MOE_CHAOS_CLEAN_SCRIPT = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
sys.path.insert(0, {repo!r})
sys.path.insert(0, os.path.join({repo!r}, "tests"))
assert len(jax.devices()) == 8, jax.devices()

from test_elastic_runtime import (_moe_batch_fn, _moe_ds_config,
                                  _moe_model_factory)
import deepspeed_tpu
from deepspeed_tpu.elasticity.runtime import BatchSpec
from deepspeed_tpu.runtime.mesh import reform_mesh

save_dir = {save_dir!r}
rec = json.loads({rec_json!r})
sp = json.loads({spec_json!r})
spec = BatchSpec(world=sp["world"], micro=sp["micro"],
                 gas=sp["gas"], total=sp["total"])
by_id = {{d.id: d for d in jax.devices()}}
devices = [by_id[i] for i in {device_ids!r}]
# plain engine, NOT a second supervisor: the oracle only needs the
# same mesh + batches + checkpoint — and the supervisor scaffolding
# (watchdog/teardown machinery) is part of what perturbs XLA-CPU's
# fragile emulated-collective runtime this test already retries over
mesh = reform_mesh(devices, {{"expert": 2}})
cfg2 = _moe_ds_config()
cfg2.pop("elasticity", None)
cfg2.pop("mesh", None)
cfg2["train_batch_size"] = spec.total
cfg2["train_micro_batch_size_per_gpu"] = spec.micro
cfg2["gradient_accumulation_steps"] = spec.gas
model, params = _moe_model_factory()
engine, _, _, _ = deepspeed_tpu.initialize(
    model=model, model_parameters=params, config=cfg2, mesh=mesh)
engine.load_checkpoint(save_dir, tag=rec["resumed_from_tag"])
assert int(engine.global_steps) == rec["resumed_step"]
clean = {{}}
for s in range(rec["resumed_step"], {end}):
    loss = engine.train_batch(batch=_moe_batch_fn(s, spec))
    clean[s] = float(jax.device_get(loss))
clean_mesh = dict(engine.mesh.shape)

print(json.dumps({{"clean_restart_losses": clean,
                  "clean_mesh": clean_mesh}}))
"""


def _moe_model_factory():
    from deepspeed_tpu.moe import MoEConfig
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForCausalLM
    import jax as _jax
    import jax.numpy as _jnp
    moe = MoEConfig(num_experts=4, top_k=2, capacity_factor=1.5,
                    every_n_layers=2).validate()
    cfg = GPT2Config(n_layer=2, n_head=2, n_embd=16, n_positions=16,
                     vocab_size=64, dropout=0.0, moe=moe,
                     dtype=_jnp.float32, param_dtype=_jnp.float32)
    model = GPT2ForCausalLM(cfg)
    params = model.module.init(
        _jax.random.PRNGKey(0),
        _jnp.zeros((4, 8), _jnp.int32), True)["params"]
    return model, params


def _moe_batch_fn(step, spec):
    rng = np.random.RandomState(2000 + step)
    ids = rng.randint(0, 64, size=(spec.gas, spec.rows, 8))
    return {"input_ids": ids.astype(np.int32)}


def _moe_ds_config():
    return {
        "steps_per_print": 10000,
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "mesh": {"expert": 2},
        "moe": {"enabled": True, "num_experts": 4, "top_k": 2,
                "capacity_factor": 1.5, "every_n_layers": 2},
        # inline saves: XLA-CPU's emulated collectives corrupt native
        # memory when the async snapshot thread's device_get races the
        # 4-axis mesh's all-to-all steps (a CPU-backend concurrency
        # artifact — bisected sync-save-fixes-it; dense 3-axis chaos
        # runs async saves fine). Real TPU runtimes don't share the
        # emulation path; the chaos contract here is the recovery
        # semantics, not the writer overlap.
        "checkpoint": {"async_save": False},
        "elasticity": {"enabled": True, "max_train_batch_size": 48,
                       "micro_batch_sizes": [2], "version": 0.1,
                       "runtime": {"enabled": True, "hosts": 4,
                                   "checkpoint_interval": 2,
                                   "drain_timeout_sec": 5.0,
                                   "escalate_after": 2}},
    }


@pytest.mark.slow
def test_moe_chaos_sigkill_bit_identical_resume(tmp_path):
    """The MoE twin of the chaos test (ISSUE 15 satellite): SIGKILL
    hosts mid-step under an EXPERT-PARALLEL run — the mesh re-forms
    on the survivors KEEPING the pinned expert axis (data absorbs the
    loss: 4x2 -> 2x2), expert state re-plans and reloads from the
    last committed checkpoint, and the post-resume loss trajectory is
    BIT-IDENTICAL to a clean engine restarted from that same
    checkpoint on the same surviving mesh (its own subprocess — see
    MOE_CHAOS_CLEAN_SCRIPT)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=8"])

    # Private per-attempt compile cache + bounded retries: XLA-CPU's
    # emulated collectives NONDETERMINISTICALLY corrupt native memory
    # under the 4-axis mesh's all-to-all programs (glibc heap aborts /
    # SIGSEGV; bisected — the dense 3-axis chaos twin never trips it),
    # and a corrupted process can poison a SHARED persistent compile
    # cache for every later run. Each attempt gets a fresh cache under
    # tmp_path; a REAL recovery-semantics regression fails all
    # attempts deterministically.
    attempts = 3
    out = None
    for attempt in range(attempts):
        cache = str(tmp_path / f"jax_cache_{attempt}")
        save_dir = str(tmp_path / f"ckpt_{attempt}")
        script = MOE_CHAOS_SCRIPT.format(repo=REPO, cache=cache,
                                         save_dir=save_dir)
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True,
                              timeout=420)
        if proc.returncode != 0:
            assert attempt < attempts - 1, proc.stderr[-3000:]
            continue
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        # the oracle gets its OWN cache: phase 1's process can be
        # internally corrupted by the emulated-collective bug and
        # serialize poisoned executables the oracle would then replay
        clean_script = MOE_CHAOS_CLEAN_SCRIPT.format(
            repo=REPO, cache=str(tmp_path / f"jax_cache_{attempt}b"),
            save_dir=save_dir,
            rec_json=json.dumps(out["recovery"]),
            spec_json=json.dumps(out["spec"]),
            device_ids=out["device_ids"], end=6)
        proc2 = subprocess.run([sys.executable, "-c", clean_script],
                               env=env, capture_output=True,
                               text=True, timeout=420)
        if proc2.returncode != 0:
            out = None
            assert attempt < attempts - 1, proc2.stderr[-3000:]
            continue
        out.update(json.loads(proc2.stdout.strip().splitlines()[-1]))
        break
    assert out is not None

    rec = out["recovery"]
    assert rec["cause"] == "host_lost"
    assert sorted(rec["lost_hosts"]) == [1, 2]
    assert rec["world_before"] == 8 and rec["world_after"] == 4
    assert rec["resumed_step"] == 2
    # the pinned expert axis survived; data absorbed the host loss
    # (4x2 -> 2x2)
    assert out["mesh_shape"]["expert"] == 2
    assert out["mesh_shape"]["data"] == 2
    assert out["clean_mesh"] == out["mesh_shape"]
    assert out["moe_active"] is True
    # expert state re-planned (the ZeRO plan priced the new world)
    assert out["zero_plan_nonzero"]
    # THE contract: post-resume losses == clean-restart losses, bitwise
    post = out["post_resume_losses"]
    clean = out["clean_restart_losses"]
    assert set(post) == set(clean) and len(post) >= 3
    for step in sorted(post):
        assert post[step] == clean[step], (
            step, post[step], clean[step],
            "MoE post-resume trajectory diverged from a clean restart")


def test_chaos_sigkill_bit_identical_resume(tmp_path):
    """SIGKILL a worker host mid-step: the supervisor must detect it,
    re-form the mesh on the 6 survivors with a re-planned ZeRO
    partition, resume from the last committed checkpoint with a loss
    trajectory BIT-IDENTICAL to a clean restart from that same
    checkpoint, and grow back to 8 devices when capacity returns."""
    cache = compile_cache_dir()
    script = CHAOS_SCRIPT.format(repo=REPO, cache=cache,
                                 save_dir=str(tmp_path / "ckpt"))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=8"])
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    rec = out["recovery"]
    assert rec["cause"] == "host_lost" and rec["lost_hosts"] == [1]
    assert rec["world_before"] == 8 and rec["world_after"] == 6
    assert rec["resumed_from_tag"] == "global_step2"
    assert rec["resumed_step"] == 2
    # recovery is seconds, not minutes (detect -> engine resumed)
    assert rec["detect_to_resume_sec"] < 60
    assert out["clean_world"] == 6
    # THE contract: post-resume losses == clean-restart losses, bitwise
    post = out["post_resume_losses"]
    clean = out["clean_restart_losses"]
    assert set(post) == set(clean) and len(post) >= 4
    for step in sorted(post):
        assert post[step] == clean[step], (
            step, post[step], clean[step],
            "post-resume trajectory diverged from a clean restart")
    # scale-up restored the original device count at a boundary
    assert out["grow_world"] == 8
    assert out["scale_ups"] and \
        out["scale_ups"][0]["world_after"] == 8
    assert out["final_losses_finite"]
