"""Memory observability (ISSUE 8): ledger, reconciliation, forensics.

Covers:
  * MemoryLedger unit behavior — register/replace/release, dynamic
    entries, per-device byte math on SHARDED arrays (shard_shape
    metadata, no sync), totals/top-buffers, reconcile + the peak
    watermark keeping the attribution snapshot taken AT peak;
  * fence alignment — the memory ledger ON (its default) adds ZERO
    per-step device_get/effects_barrier calls and the fenced window
    still pays exactly ONE device_get per fence (the PR 2/5 guard,
    extended);
  * the `memory` event schema round-tripping through BOTH sinks
    (JSONL parse + native tfevents scalars);
  * Perfetto per-category counter tracks through the Chrome-trace
    schema validator, plus `ds_trace summary`'s memory section;
  * engine registration across modes — bf16 mixed precision, gas>1
    accumulators, ZeRO-Offload host masters/moments + wire
    residual/shadow, checkpoint snapshot double-buffers alive only
    between snapshot and commit;
  * plan-vs-measured — ZeroShardingPolicy.memory_plan vs the live
    ledger vs REAL per-device shard bytes within a pinned tolerance;
  * OOM forensics — classification units and a subprocess run with an
    injected allocator failure whose flight dump names the top ledger
    categories and actionable hints;
  * the see_memory_usage consolidation + host-RSS fallback satellites.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from simple_model import SimpleModel
from deepspeed_tpu.monitor import Monitor, memory as mem
from deepspeed_tpu.monitor.flight import list_flight_dumps
from deepspeed_tpu.monitor.memory import (MemoryLedger, classify_oom,
                                          host_rss_bytes, leaf_nbytes,
                                          oom_hints, plan_vs_measured,
                                          tree_nbytes)
from deepspeed_tpu.monitor.tfevents import read_tfevents
from deepspeed_tpu.monitor.trace_export import summarize_trace
from test_trace_export import validate_chrome_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------
# helpers (the test_monitor.py engine shape)
# ----------------------------------------------------------------------
def _make_stacked(seed, bs=16, dim=8):
    rng = np.random.RandomState(seed)
    x = rng.randn(bs, dim).astype(np.float32)
    return {"x": x[None], "y": (x * 0.5)[None]}


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
# byte arithmetic
# ----------------------------------------------------------------------
def test_leaf_nbytes_shapes_and_dtypes():
    assert leaf_nbytes(np.zeros((4, 8), np.float32)) == 4 * 8 * 4
    assert leaf_nbytes(
        jax.ShapeDtypeStruct((16,), jnp.bfloat16)) == 32
    assert leaf_nbytes(object()) == 0
    tree = {"a": np.zeros((2, 2), np.float32),
            "b": [jnp.zeros((3,), jnp.int32)]}
    assert tree_nbytes(tree) == 16 + 12


def test_leaf_nbytes_sharded_is_per_device():
    """A data-sharded array counts ONE device's shard; a replicated
    array counts full size — exactly its per-chip cost."""
    from jax.sharding import NamedSharding, PartitionSpec
    from deepspeed_tpu.runtime.mesh import build_mesh
    n_dev = len(jax.devices())
    if n_dev < 2:
        pytest.skip("needs a multi-device mesh")
    mesh = build_mesh({"pipe": 1, "data": n_dev, "model": 1})
    x = jax.device_put(
        np.zeros((n_dev * 4, 8), np.float32),
        NamedSharding(mesh, PartitionSpec("data", None)))
    assert leaf_nbytes(x) == 4 * 8 * 4                 # 1/n_dev shard
    assert leaf_nbytes(x, per_device=False) == n_dev * 4 * 8 * 4
    r = jax.device_put(np.zeros((8,), np.float32),
                       NamedSharding(mesh, PartitionSpec()))
    assert leaf_nbytes(r) == 32                        # replicated


def test_host_rss_bytes_reads_statm():
    rss = host_rss_bytes()
    assert rss is not None and rss > 1 << 20           # >1 MiB resident


# ----------------------------------------------------------------------
# ledger unit behavior
# ----------------------------------------------------------------------
def test_ledger_register_release_totals_top():
    led = MemoryLedger()
    t1 = led.register(mem.CAT_PARAMS, "p", 100)
    led.register(mem.CAT_OPT, "o", 300)
    led.register(mem.CAT_HOST_MASTER, "hm", 50, space=mem.SPACE_HOST)
    totals = led.totals()
    assert totals[mem.SPACE_HBM] == {"params": 100, "opt_state": 300}
    assert totals[mem.SPACE_HOST] == {"host_master": 50}
    top = led.top_buffers(2)
    assert [b["name"] for b in top] == ["o", "p"]
    # same (category, name) replaces, release drops, unknown is a no-op
    led.register(mem.CAT_PARAMS, "p", 700)
    assert led.totals()[mem.SPACE_HBM]["params"] == 700
    led.release(t1)
    assert "params" not in led.totals()[mem.SPACE_HBM]
    led.release(("nope", "nothing"))
    led.release(None)


def test_ledger_dynamic_entry_sampled_and_fault_isolated():
    led = MemoryLedger()
    vals = {"n": 5}
    led.register_dynamic(mem.CAT_PREFETCH, "q", lambda: vals["n"] * 10)
    assert led.totals()[mem.SPACE_HBM]["prefetch"] == 50
    vals["n"] = 2
    assert led.totals()[mem.SPACE_HBM]["prefetch"] == 20
    led.register_dynamic(mem.CAT_PREFETCH, "boom", lambda: 1 / 0)
    assert led.totals()[mem.SPACE_HBM]["prefetch"] == 20


def test_ledger_reconcile_residual_and_peak_attribution():
    """The peak watermark keeps the attribution snapshot taken AT the
    fence that observed the peak — not the current composition."""
    led = MemoryLedger()
    led.register(mem.CAT_PARAMS, "p", 400)
    tok = led.register(mem.CAT_CKPT, "snap", 600)
    # 2 devices, 1500 in use EACH: the ledger is per-device, so the
    # residual compares against in_use / device_count, not the sum
    pay = led.reconcile({"in_use_bytes": 3000, "peak_bytes": 2000,
                         "device_count": 2}, rss=None, step=10)
    assert pay["hbm"]["ledger_bytes"] == 1000
    assert pay["hbm"]["measured_in_use"] == 3000
    assert pay["hbm"]["measured_in_use_per_device"] == 1500
    assert pay["hbm"]["residual_bytes"] == 500
    assert pay["peak"]["bytes"] == 2000
    assert pay["peak"]["categories"] == {"params": 400,
                                         "ckpt_snapshot": 600}
    # snapshot released, allocator lower: the PEAK attribution persists
    led.release(tok)
    pay = led.reconcile({"in_use_bytes": 400, "peak_bytes": 2000,
                         "device_count": 2}, rss=None, step=20)
    assert pay["hbm"]["categories"] == {"params": 400}
    assert pay["peak"]["step"] == 10
    assert pay["peak"]["categories"]["ckpt_snapshot"] == 600
    # a HIGHER peak re-attributes
    pay = led.reconcile({"in_use_bytes": 3000, "peak_bytes": 3000,
                         "device_count": 2}, rss=None, step=30)
    assert pay["peak"]["step"] == 30
    assert "ckpt_snapshot" not in pay["peak"]["categories"]


def test_ledger_reconcile_host_fallback_off_device():
    """device_count == 0 (backend exposes no memory_stats): the
    reconciliation falls back to host RSS — the gauge stays meaningful
    off-TPU."""
    led = MemoryLedger()
    led.register(mem.CAT_HOST_MASTER, "m", 1 << 20,
                 space=mem.SPACE_HOST)
    pay = led.reconcile({"in_use_bytes": 0, "peak_bytes": 0,
                         "device_count": 0,
                         "host_rss_bytes": 8 << 20}, step=1)
    assert pay["hbm"]["measured_in_use"] is None
    assert pay["host"]["rss_bytes"] == 8 << 20
    assert pay["host"]["residual_bytes"] == 7 << 20
    assert pay["peak"]["space"] == mem.SPACE_HOST
    assert pay["peak"]["bytes"] == 8 << 20


def test_plan_vs_measured_deltas():
    out = plan_vs_measured({"params": 1000, "master": 0},
                           {"params": 1100, "extra": 7})
    assert out["params"]["delta_pct"] == 10.0
    assert out["master"]["delta_pct"] is None      # planned 0
    assert out["extra"]["planned_bytes"] is None


# ----------------------------------------------------------------------
# OOM classification units
# ----------------------------------------------------------------------
def test_classify_oom_markers():
    assert classify_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes"))
    assert classify_oom(MemoryError())
    assert classify_oom(RuntimeError("Failed to allocate 4.2GiB"))
    assert classify_oom(RuntimeError("hbm OOM at step 4"))
    assert not classify_oom(ValueError("shape mismatch"))
    assert not classify_oom(RuntimeError("INVALID_ARGUMENT: nope"))
    # "OOM" only as a word: ordinary messages must not trigger
    # memory forensics
    assert not classify_oom(RuntimeError("no room left in ring"))
    assert not classify_oom(RuntimeError("zoom factor wrong"))


def test_oom_hints_name_the_dominant_knob():
    gib = 1 << 30
    pay = {"hbm": {"categories": {"params": gib,
                                  "ckpt_snapshot": 2 * gib},
                   "ledger_bytes": 3 * gib,
                   "measured_in_use": 16 * gib,
                   "measured_in_use_per_device": 16 * gib,
                   "residual_bytes": 13 * gib},
           "host": {"categories": {}}}
    hints = oom_hints(pay)
    text = " ".join(hints)
    assert "save_fused_epilogues" in text          # residual dominates
    assert "writer_queue_depth" in text            # snapshot alive
    # a payload with nothing dominant still says something actionable
    assert oom_hints({"hbm": {"categories": {}}, "host": {}})


# ----------------------------------------------------------------------
# fence alignment guards (memory ledger ON is the default)
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


def test_memory_ledger_keeps_hot_path_sync_free(tmp_path, monkeypatch):
    """Reconciliation is fence-aligned host arithmetic: with the
    ledger ON (default), N steps between fences perform ZERO
    device_get/effects_barrier calls and a fenced window still costs
    exactly ONE device_get per fence."""
    engine = _engine(
        {"bf16": {"enabled": True},
         "async_dispatch": {"enabled": True, "steps_per_sync": 4}},
        monitor={"enabled": True, "sinks": ["jsonl"],
                 "output_path": str(tmp_path)})
    assert engine.monitor.memory_enabled
    batches = [engine.stage_batch(_make_stacked(i)) for i in range(16)]
    for b in batches[:8]:
        engine.train_batch(batch=b)
    assert engine._host_steps == 8    # next fences at 12 and 16
    counters = _SyncCounters(monkeypatch)
    for b in batches[8:]:
        engine.train_batch(batch=b)
    assert counters.device_get == 2, \
        f"expected 1 device_get per fence (2 fences), got " \
        f"{counters.device_get}"
    assert counters.effects_barrier == 0
    log = os.path.join(str(tmp_path), "events.jsonl")
    kinds = [json.loads(l)["kind"] for l in open(log)]
    assert kinds.count("memory") >= 2
    engine.monitor.close()


# ----------------------------------------------------------------------
# event schema through both sinks
# ----------------------------------------------------------------------
def test_memory_event_schema_jsonl_and_tfevents(tmp_path):
    import glob
    engine = _engine(
        {"bf16": {"enabled": True},
         "async_dispatch": {"enabled": True, "steps_per_sync": 2}},
        monitor={"enabled": True, "sinks": ["jsonl", "tensorboard"],
                 "output_path": str(tmp_path)})
    for i in range(4):
        engine.train_batch(batch=_make_stacked(i))
    engine.monitor.close()

    events = [json.loads(l) for l in
              open(os.path.join(str(tmp_path), "events.jsonl"))]
    mems = [e for e in events if e["kind"] == "memory"]
    assert mems
    for e in mems:
        assert e["v"] == 1 and isinstance(e["step"], int)
        for space in ("hbm", "host"):
            blk = e[space]
            for key in ("categories", "ledger_bytes",
                        "residual_bytes"):
                assert key in blk, (space, key, e)
        assert {"params", "master", "opt_state"} <= \
            set(e["hbm"]["categories"])
        assert e["hbm"]["ledger_bytes"] == \
            sum(e["hbm"]["categories"].values())
        assert e["host"]["rss_bytes"] > 0     # the off-TPU fallback
        assert isinstance(e["top_buffers"], list) and e["top_buffers"]
        assert e["peak"] is None or "categories" in e["peak"]

    tb = glob.glob(os.path.join(str(tmp_path), "tb",
                                "events.out.tfevents.*"))
    assert tb
    tags = set()
    for ev in read_tfevents(tb[0]):
        tags |= set(ev.get("scalars", {}))
    assert "monitor/memory/hbm/ledger_bytes" in tags
    assert "monitor/memory/hbm/categories/params" in tags
    assert "monitor/memory/host/rss_bytes" in tags


def test_snapshot_carries_memory_ledger(tmp_path):
    engine = _engine({"bf16": {"enabled": True}},
                     monitor={"enabled": True, "sinks": [],
                              "output_path": str(tmp_path)})
    engine.train_batch(batch=_make_stacked(0))
    snap = engine.monitor.snapshot()
    assert set(snap) == set(Monitor.SNAPSHOT_KEYS)
    led = snap["memory_ledger"]
    assert led["hbm"]["categories"]["params"] > 0
    # memory off -> stable key, None value
    engine2 = _engine({"bf16": {"enabled": True}},
                      monitor={"enabled": True, "sinks": [],
                               "output_path": str(tmp_path),
                               "memory": {"enabled": False}})
    engine2.train_batch(batch=_make_stacked(0))
    snap2 = engine2.monitor.snapshot()
    assert set(snap2) == set(Monitor.SNAPSHOT_KEYS)
    assert snap2["memory_ledger"] is None
    engine.monitor.close()
    engine2.monitor.close()


# ----------------------------------------------------------------------
# Perfetto counter tracks + ds_trace summary
# ----------------------------------------------------------------------
def test_memory_counter_tracks_validate_and_summarize(tmp_path,
                                                      capsys):
    engine = _engine(
        {"bf16": {"enabled": True},
         "async_dispatch": {"enabled": True, "steps_per_sync": 2}},
        monitor={"enabled": True, "sinks": ["jsonl"],
                 "output_path": str(tmp_path),
                 "trace": {"enabled": True}})
    plan = {"params": 100, "master": 200, "opt_state": 400}
    engine.monitor.set_memory_plan(plan)
    for i in range(4):
        engine.train_batch(batch=_make_stacked(i))
    path = engine.monitor.export_trace()
    engine.monitor.close()

    doc = json.load(open(path))
    validate_chrome_trace(doc)
    counters = [e for e in doc["traceEvents"]
                if e.get("ph") == "C" and
                e["name"] in ("hbm_bytes", "host_bytes")]
    assert counters
    hbm = [e for e in counters if e["name"] == "hbm_bytes"]
    assert hbm and {"params", "master", "opt_state"} <= \
        set(hbm[0]["args"])
    assert doc["otherData"]["memory_plan"] == plan

    s = summarize_trace(doc)
    assert "memory" in s
    assert s["memory"]["hbm_bytes"]["params"]["peak_bytes"] > 0
    pvm = s["memory"]["plan_vs_measured"]
    assert pvm["params"]["measured_bytes"] > 0
    assert pvm["params"]["delta_pct"] is not None

    # the plan survives a multi-rank merge (promoted like `pipeline`)
    from deepspeed_tpu.monitor.trace_export import merge_traces
    merged = summarize_trace(merge_traces([doc]))
    assert "plan_vs_measured" in merged["memory"]

    # the CLI prints the memory section
    from deepspeed_tpu.monitor.trace_cli import main as trace_main
    assert trace_main(["summary", path]) == 0
    out = capsys.readouterr().out
    assert "memory (hbm):" in out
    assert "plan vs measured" in out


def test_memory_counter_emits_zero_for_released_category(tmp_path):
    """Chrome counter semantics keep the last value per key: a
    released buffer must emit one explicit 0, or the stacked area (and
    summarize_trace's 'last') stays at its old height forever."""
    engine = _engine(
        {"bf16": {"enabled": True},
         "async_dispatch": {"enabled": True, "steps_per_sync": 1}},
        monitor={"enabled": True, "sinks": [],
                 "output_path": str(tmp_path),
                 "trace": {"enabled": True}})
    tok = engine.monitor.ledger.register(mem.CAT_CKPT, "snap", 1234)
    engine.train_batch(batch=_make_stacked(0))
    engine.monitor.ledger.release(tok)
    engine.train_batch(batch=_make_stacked(1))
    doc = engine.monitor.trace_export.to_dict()
    hbm = [e for e in doc["traceEvents"]
           if e.get("ph") == "C" and e["name"] == "hbm_bytes"]
    assert hbm[0]["args"]["ckpt_snapshot"] == 1234
    assert hbm[1]["args"]["ckpt_snapshot"] == 0
    s = summarize_trace(doc)
    assert s["memory"]["hbm_bytes"]["ckpt_snapshot"]["last_bytes"] == 0
    assert s["memory"]["hbm_bytes"]["ckpt_snapshot"]["peak_bytes"] == \
        1234
    engine.monitor.close()


def test_summarize_memory_counters_keep_ranks_apart():
    """Counters from different ranks merge by per-key MAX (per-device
    semantics), not by interleaved last-wins."""
    from deepspeed_tpu.monitor.trace_export import (TraceExporter,
                                                    merge_traces)
    ex0 = TraceExporter(rank=0)
    ex1 = TraceExporter(rank=1)
    ex0.counter("memory", "hbm_bytes", {"params": 100})
    ex1.counter("memory", "hbm_bytes", {"params": 700})
    ex0.counter("memory", "hbm_bytes", {"params": 50})
    s = summarize_trace(merge_traces([ex0.to_dict(), ex1.to_dict()]))
    row = s["memory"]["hbm_bytes"]["params"]
    # rank 0's last is 50, rank 1's 700: the merge reports the binding
    # per-device number, never rank 0's tail overwriting rank 1's
    assert row["last_bytes"] == 700
    assert row["peak_bytes"] == 700
    assert s["memory"]["ranks"] == 2


# ----------------------------------------------------------------------
# engine registration across modes
# ----------------------------------------------------------------------
def test_engine_registers_state_groups_bf16(tmp_path):
    engine = _engine({"bf16": {"enabled": True}},
                     monitor={"enabled": True, "sinks": [],
                              "output_path": str(tmp_path)})
    cats = engine.monitor.ledger.totals()[mem.SPACE_HBM]
    assert cats["params"] > 0
    assert cats["master"] > 0          # mixed precision: fp32 masters
    assert cats["opt_state"] > cats["master"]   # 2 moments + master-ish
    assert "grads" not in cats         # gas=1: no persistent accumulator
    engine.monitor.close()


def test_engine_registers_grad_accumulator_gas2(tmp_path):
    engine = _engine(
        {"bf16": {"enabled": True},
         "train_batch_size": 32,
         "gradient_accumulation_steps": 2},
        monitor={"enabled": True, "sinks": [],
                 "output_path": str(tmp_path)})
    cats = engine.monitor.ledger.totals()[mem.SPACE_HBM]
    assert cats["grads"] > 0
    engine.monitor.close()


def test_offload_registers_host_state_and_wire(tmp_path):
    engine = _engine(
        {"bf16": {"enabled": True},
         "zero_optimization": {"stage": 2, "cpu_offload": True,
                               "offload_wire": {"grad_bits": 1,
                                                "param_bits": 8}}},
        monitor={"enabled": True, "sinks": [],
                 "output_path": str(tmp_path)})
    totals = engine.monitor.ledger.totals()
    host = totals[mem.SPACE_HOST]
    hbm = totals[mem.SPACE_HBM]
    n = engine._host_master.size
    assert host["host_master"] == n * 4
    assert host["host_opt_state"] == 2 * n * 4
    # 1-bit residual (device) + int8 shadow (host) + device flat copy
    assert hbm["wire"] >= engine._offload_grad_residual.nbytes
    assert host["wire"] == engine._offload_param_shadow.nbytes
    names = {b["name"] for b in engine.monitor.ledger.top_buffers(20)}
    assert {"offload.host_master", "offload.adam_moments",
            "offload.grad_residual", "offload.param_shadow",
            "offload.device_flat"} <= names
    engine.monitor.close()


def test_ckpt_snapshot_registered_then_released(tmp_path):
    engine = _engine({"bf16": {"enabled": True}},
                     monitor={"enabled": True, "sinks": [],
                              "output_path": str(tmp_path)})
    engine.train_batch(batch=_make_stacked(0))
    led = engine.monitor.ledger
    assert "ckpt_snapshot" not in led.totals()[mem.SPACE_HBM]
    # a paused writer holds the snapshot alive; the category must be
    # visible exactly while the double-buffers exist
    import threading
    gate = threading.Event()
    orig = engine._write_checkpoint

    def slow_write(*a, **kw):
        gate.wait(timeout=30)
        return orig(*a, **kw)

    engine._write_checkpoint = slow_write
    assert engine.save_checkpoint(str(tmp_path / "ckpt"),
                                  async_save=True)
    cats = led.totals()[mem.SPACE_HBM]
    assert cats.get("ckpt_snapshot", 0) > 0
    gate.set()
    engine.wait_for_checkpoint()
    assert "ckpt_snapshot" not in led.totals()[mem.SPACE_HBM]
    engine.monitor.close()


def test_prefetch_buffer_bytes_dynamic_entry(tmp_path):
    engine = _engine(
        {"bf16": {"enabled": True}},
        monitor={"enabled": True, "sinks": [],
                 "output_path": str(tmp_path)})
    micro = [{k: v[0] for k, v in _make_stacked(i).items()}
             for i in range(6)]
    loader = engine.prefetch(iter(micro))
    engine.train_batch(data_iter=loader)
    # the worker runs ahead: wait until something is queued + sized
    import time
    deadline = time.time() + 5.0
    while time.time() < deadline and \
            (not loader.staged_nbytes or not loader.occupancy()):
        time.sleep(0.02)
    assert loader.staged_nbytes > 0
    cats = engine.monitor.ledger.totals()[mem.SPACE_HBM]
    assert cats.get("prefetch", 0) == \
        loader.occupancy() * loader.staged_nbytes
    loader.close()
    engine.monitor.close()


def test_pipe_1f1b_registers_buffer_bytes():
    """The compiled 1F1B executor's per-stage carry (saved-input
    recompute buffers + delivery rings) registers under pipe_buffers
    once the interpreter compiles — the schedule's activation bound,
    attributed."""
    import flax.linen as nn
    from deepspeed_tpu.runtime.pipe.module import (LayerSpec,
                                                   PipelineModule)
    if len(jax.devices()) < 4:
        pytest.skip("needs the multi-device mesh")

    def mse(pred, labels):
        return jnp.mean((pred.astype(jnp.float32) -
                         labels.astype(jnp.float32)) ** 2)

    module = PipelineModule(
        [LayerSpec(nn.Dense, 16), jnp.tanh, LayerSpec(nn.Dense, 8)],
        num_stages=2, loss_fn=mse, partition_method="uniform")
    rng = np.random.RandomState(0)
    params = module.init_params(
        jax.random.PRNGKey(0), jnp.asarray(rng.randn(4, 16),
                                           jnp.float32))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=module, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 8,
                "gradient_accumulation_steps": 2,
                "steps_per_print": 1000,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "mesh": {"pipe": 2, "data": 4, "model": 1},
                "monitor": {"enabled": True, "sinks": []}})
    assert "pipe_buffers" not in \
        engine.monitor.ledger.totals()[mem.SPACE_HBM]
    x = rng.randn(16, 16).astype(np.float32)
    w = np.linspace(-1, 1, 16 * 8).reshape(16, 8).astype(np.float32)
    engine.train_batch(batch={"x": x, "y": x @ w})
    cats = engine.monitor.ledger.totals()[mem.SPACE_HBM]
    bm = engine._interp_fn.buffer_meta
    assert cats["pipe_buffers"] == bm["bytes_per_stage"] > 0
    # the bound in the meta is the schedule's, not an ad-hoc number
    from deepspeed_tpu.runtime.pipe.interp import num_pipe_buffers
    assert bm["saved_input_buffers"] == num_pipe_buffers(2, 2)
    engine.monitor.close()


# ----------------------------------------------------------------------
# plan vs measured on the live mesh (the 3B-analogue executed check)
# ----------------------------------------------------------------------
def test_memory_plan_agrees_with_ledger_and_measured():
    """ZeroShardingPolicy.memory_plan vs the ledger vs REAL per-device
    shard bytes, through the exact 13B code path (bf16 master-less
    ZeRO-3) at CI scale — pinned to 15% (count scalars and replicated
    tiny leaves are the only slack)."""
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config
    from deepspeed_tpu.runtime.mesh import build_mesh
    n_dev = len(jax.devices())
    if n_dev < 2:
        pytest.skip("needs a multi-device mesh")
    mesh = build_mesh({"pipe": 1, "data": n_dev, "model": 1})
    cfg = gpt2_config("gpt2-125m", dropout=0.0, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, vocab_size=512,
                      n_positions=64, n_layer=2)
    model = GPT2ForCausalLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0),
        {"input_ids": np.zeros((n_dev, 64), np.int32)})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, mesh=mesh,
        config={
            "train_micro_batch_size_per_gpu": n_dev,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 1000,
            "bf16": {"enabled": True, "master_weights": False},
            "zero_optimization": {"stage": 3},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "monitor": {"enabled": True, "sinks": []},
        })
    del params
    shapes = jax.eval_shape(lambda t: t, engine.state.params)
    plan = engine.zero_policy.memory_plan(shapes, compute_bytes=2,
                                          sr_mode=True, gas=1)
    cats = engine.monitor.ledger.totals()[mem.SPACE_HBM]

    dev0 = jax.devices()[0]

    def dev_bytes(tree):
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            if isinstance(leaf, jax.Array):
                for sh in leaf.addressable_shards:
                    if sh.device == dev0:
                        total += sh.data.nbytes
        return total

    measured = {"params": dev_bytes(engine.state.params),
                "opt_state": dev_bytes(engine.state.opt_state)}
    for scored in (plan_vs_measured(plan, cats),
                   plan_vs_measured(plan, measured)):
        for comp in ("params", "opt_state"):
            assert scored[comp]["delta_pct"] is not None, scored
            assert abs(scored[comp]["delta_pct"]) < 15.0, \
                (comp, scored)
    engine.monitor.close()


def test_13b_plan_agrees_with_the_closed_form():
    """GPT-2 13B over a data mesh of 128, from abstract shapes
    (`eval_shape`: nothing of that size is allocated):
    `ZeroShardingPolicy.memory_plan` in the master-less bfloat16 mode
    against the closed form 6 bytes a parameter over dp (padding of
    the leaves that do not divide makes the plan slightly larger,
    never smaller), and with float32 masters the state of one device
    stays under 2 GiB: the feasibility number
    `docs/tutorials/zero.md` leans on."""
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config
    from deepspeed_tpu.runtime.zero.partition import ZeroShardingPolicy
    cfg = gpt2_config("gpt2-13b", n_positions=1024, dropout=0.0)
    shapes = jax.eval_shape(
        lambda: GPT2ForCausalLM(cfg).init(
            jax.random.PRNGKey(0),
            {"input_ids": np.zeros((1, 1024), np.int32)}))

    class MeshShim:   # the axis sizes are all the policy's math needs
        shape = {"pipe": 1, "data": 128, "model": 1}

    policy = ZeroShardingPolicy(MeshShim(), 3)
    n = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(shapes))
    assert n > 12e9
    plan = policy.memory_plan(shapes, compute_bytes=2, sr_mode=True,
                              gas=1)
    closed_form = 6.0 * n / 128
    planned = plan["params"] + plan["opt_state"]
    assert 0.0 <= (planned - closed_form) / closed_form < 0.05
    with_masters = policy.memory_plan(shapes, compute_bytes=2,
                                      sr_mode=False, gas=1)
    per_device = with_masters["params"] + with_masters["master"] + \
        with_masters["opt_state"]
    assert 14.0 * n / 128 <= per_device < 2 * 2**30


# ----------------------------------------------------------------------
# subprocess OOM-classification flight dump
# ----------------------------------------------------------------------
def test_subprocess_oom_crash_dumps_attributed_flight(tmp_path):
    """An injected allocator failure (RESOURCE_EXHAUSTED out of the
    jitted step — the XlaRuntimeError text) must leave a flight dump
    classified as reason "oom" carrying the ledger categories, the top
    buffers, and actionable hints."""
    out = str(tmp_path / "mon")
    script = f"""
import os, sys, json
import numpy as np
import jax
jax.config.update('jax_platforms', 'cpu')
sys.path.insert(0, {REPO!r})
sys.path.insert(0, os.path.join({REPO!r}, 'tests'))
import deepspeed_tpu
from simple_model import SimpleModel

def mk(seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(16, 8).astype(np.float32)
    return {{"x": x[None], "y": (x * 0.5)[None]}}

model = SimpleModel(hidden_dim=8)
cfg = {{"train_batch_size": 16, "steps_per_print": 10000,
       "bf16": {{"enabled": True}},
       "optimizer": {{"type": "Adam", "params": {{"lr": 1e-2}}}},
       "async_dispatch": {{"enabled": True, "steps_per_sync": 1}},
       "monitor": {{"enabled": True, "sinks": ["jsonl"],
                   "output_path": {out!r}}}}}
e, _, _, _ = deepspeed_tpu.initialize(
    model=model, model_parameters=model.params, config=cfg)
for i in range(3):
    e.train_batch(batch=mk(i))

# injected allocator failure: the step fn raises what jaxlib's
# XlaRuntimeError carries on a real HBM exhaustion
real_step = e._fused_step_jit
def oom_step(*a, **kw):
    raise RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "17179869184 bytes.")
e._fused_step_jit = oom_step
e.train_batch(batch=mk(9))
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode != 0
    assert "RESOURCE_EXHAUSTED" in proc.stderr
    dumps = list_flight_dumps(out)
    assert dumps, "OOM subprocess left no flight dump"
    docs = [json.load(open(p)) for p in dumps]
    ooms = [d for d in docs if d["reason"] == "oom"]
    assert ooms, [d["reason"] for d in docs]
    doc = ooms[-1]
    oom = doc["extra"]["oom"]
    # the ledger categories survive into the dump with real bytes
    assert oom["hbm"]["categories"]["params"] > 0
    assert oom["hbm"]["categories"]["opt_state"] > 0
    top_cats = {b["category"] for b in oom["top_buffers"]}
    assert {"params", "master", "opt_state"} <= top_cats
    assert oom["hints"] and all(isinstance(h, str)
                                for h in oom["hints"])
    # the sticky peak context rode along too (set at every fence)
    assert "memory_peak" in doc["context"]
    assert doc["extra"]["error"].startswith("RuntimeError")


# ----------------------------------------------------------------------
# satellites: see_memory_usage consolidation + RSS fallback
# ----------------------------------------------------------------------
class _CollectLog:
    """Capture DeepSpeedTPU log lines (the logger does not propagate,
    so caplog misses it — the test_monitor _Collect pattern)."""

    def __enter__(self):
        import logging
        from deepspeed_tpu.utils.logging import logger

        class H(logging.Handler):
            def __init__(self):
                super().__init__()
                self.lines = []

            def emit(self, record):
                self.lines.append(record.getMessage())

        self._logger = logger
        self._h = H()
        logger.addHandler(self._h)
        return self._h.lines

    def __exit__(self, *exc):
        self._logger.removeHandler(self._h)
        return False


def test_see_memory_usage_aggregates_all_devices(monkeypatch):
    """see_memory_usage now rides device_memory_stats: SUM of in-use
    over all local devices (it used to read only device 0)."""

    class FakeDev:
        def __init__(self, in_use, peak):
            self._s = {"bytes_in_use": in_use,
                       "peak_bytes_in_use": peak}

        def memory_stats(self):
            return self._s

    gib = 1024 ** 3
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [FakeDev(1 * gib, 2 * gib),
                                 FakeDev(3 * gib, 5 * gib)])
    from deepspeed_tpu.runtime.utils import see_memory_usage
    with _CollectLog() as lines:
        see_memory_usage("probe", force=True)
    text = " ".join(lines)
    assert "4.00 GB" in text and "5.00 GB" in text
    assert "2 local devices" in text


def test_see_memory_usage_host_rss_fallback(monkeypatch):
    class NoStatsDev:
        def memory_stats(self):
            return None

    monkeypatch.setattr(jax, "local_devices", lambda: [NoStatsDev()])
    from deepspeed_tpu.runtime.utils import see_memory_usage
    with _CollectLog() as lines:
        see_memory_usage("probe", force=True)
    assert any("host RSS" in l for l in lines)


def test_device_memory_stats_carries_host_rss():
    from deepspeed_tpu.utils.timer import device_memory_stats
    stats = device_memory_stats()
    assert stats.get("host_rss_bytes", 0) > 1 << 20
