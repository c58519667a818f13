"""What ISSUE 31 adds to the benchmark, driven on the CPU at a tiny
size (`tiny_falcon_h1.py`): the Falcon-H1 cell end to end through the
kind `serve_open_arch`; the fp8 reference, a bfloat16 state, a state
dropped at a chunk boundary, stale convolution rows and a K/V page
written to the wrong slot each not correct; the new readers' region
list against the program's scopes and on a trace made by hand with
both launches in it; the cost functions against hand counts; the
files."""

import json
import os
import time

import numpy as np
import pytest

import tiny_copy
import tiny_falcon_h1
from benchmark import (harness, region_join, scope_reduce, ssm_costs,
                       state_scopes, trace_reduce)
from deepspeed_tpu.monitor import programs

SEED = 2**31 + 77
REPO = tiny_copy.REPO
CELL = tiny_falcon_h1.FULL_CELL
# the readers this PR brings; BENCHMARK.json lists the first two (a
# traced tail holds a prefill launch on nine seeds of ten only, so
# `ssm_prefill_roofline` waits for a tail that always holds one:
# PERF.md section 7)
NEW = ("ssm_state_time_share.serve", "ssm_decode_roofline",
       "ssm_prefill_roofline")
LISTED = NEW[:2]


@pytest.fixture()
def tiny(tmp_path, monkeypatch):
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    monkeypatch.setattr(state_scopes, "_last", (None, None))
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    return tiny_copy.point_harness_at(monkeypatch,
                                      tiny_falcon_h1.make(tmp_path))


def run(h, **kw):
    return h.run_cell(tiny_falcon_h1.CELL, SEED, 2.0, kw.pop("trace", 0),
                      time.time(), need_tpu=False, keep_checks=True, **kw)


def test_kind_runs_end_to_end(tiny):
    result = run(tiny)
    assert result["correct"] and result["failed"] == 0, result["checks"]
    assert result["attempted"] == 8
    assert set(result["metrics"]) == {"itl_mean_ms", "serve_tokens_per_s",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert {c["name"] for c in result["checks"]} >= {
        "served_gap_max", "served_gap_mean"}


def test_fp8_control_run_is_not_correct(tiny):
    result = run(tiny, control=1)
    assert not result["correct"] and result["failed"] == 0
    assert any(c["name"] == "served_gap_max" and not c["ok"]
               for c in result["checks"])


def test_traced_run_reports_what_the_cpu_can_read(tiny):
    """The CPU's profile has no device plane: the device_trace readers
    have nothing to read and are left out; the counters and the
    existing serving readers are there."""
    result = run(tiny, trace=1)
    assert result["correct"]
    got = result["metrics"]
    assert got["program_temp_gb.serve"]["value"] == pytest.approx(
        programs.memory("jit_decode_fn")["temp"] / 1e9)
    assert set(got) >= {"ttft_observed_mean_ms", "itl_p95_ms",
                        "slots_occupied_mean", "compiles_in_window.serve",
                        "peak_hbm_gb.serve", "queue_wait_mean_ms"}
    assert not set(NEW) & set(got)


# ----------------------------------------------------------------------
# faults, each read against the sound run's limits
# ----------------------------------------------------------------------
def drop_state_at_chunk_boundaries(monkeypatch):
    """Every prefill launch starts from zero state: what a program
    that lost the state between launches would compute."""
    from deepspeed_tpu.inference import engine as engine_mod
    real = engine_mod.ssd_chunked

    def dropped(xs, dt, A, B, C, D, H0, *a, **kw):
        return real(xs, dt, A, B, C, D, 0 * H0, *a, **kw)
    monkeypatch.setattr(engine_mod, "ssd_chunked", dropped)


def stale_conv_rows(monkeypatch):
    """Every call of the convolution sees zeros where the rows before
    its first token belong: what a program that did not carry them
    would compute."""
    from deepspeed_tpu.inference import engine as engine_mod
    real = engine_mod.causal_conv

    def stale(x, w, b, carried, *a):
        return real(x, w, b, 0 * carried, *a)
    monkeypatch.setattr(engine_mod, "causal_conv", stale)


def page_written_to_the_wrong_slot(engine):
    """Slot 0's first page swapped with slot 1's in the pools (both
    K and V, every layer), the tables left alone: what a prefill that
    wrote through the wrong table row would leave."""
    a, b = (int(engine.cache.tables[s][0]) for s in (0, 1))
    for key in ("k_pool", "v_pool"):
        pool = engine._state[key]
        engine._state[key] = pool.at[:, a].set(pool[:, b]).at[:, b].set(
            pool[:, a])


FAULTS = [None, "fp8_reference", "bfloat16_state",
          "state_dropped_at_chunk_boundary", "stale_conv_rows",
          "page_written_to_the_wrong_slot"]


@pytest.mark.parametrize("fault", FAULTS)
def test_live_slots_against_the_reference(tiny, monkeypatch, fault):
    """Slots in mid-flight, prompts of several launches behind them
    and tens of decode steps through both caches: sound float32 agrees
    with the reference to rounding on the logits and on layer 0's
    state, element for element; each fault lies at least 10 times
    past a limit (the two of the state's type and carrying on the
    state, the page on the logits alone)."""
    from benchmark.kinds import serve_open, serve_open_arch
    from deepspeed_tpu.inference import Request, ServingLoop
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_falcon_h1.CELL)
    control = cell["mix"]["control_program"] \
        if fault == "bfloat16_state" else None
    if fault == "state_dropped_at_chunk_boundary":
        drop_state_at_chunk_boundaries(monkeypatch)
    if fault == "stale_conv_rows":
        stale_conv_rows(monkeypatch)
    engine, flat, ref = serve_open_arch.build_engine(cell, SEED, control)
    assert str(engine._state["ssm_state"].dtype) == (
        "bfloat16" if fault == "bfloat16_state" else "float32")
    loop = ServingLoop(engine)
    rng = np.random.default_rng(3)
    for i, n in enumerate((20, 45, 70)):
        loop.submit(Request(rid=i, tokens=rng.integers(0, 512, n),
                            max_new_tokens=40))
    loop._t0, loop._last_fence_t = time.monotonic(), 0.0
    for _ in range(12):
        loop.step()
    if fault == "page_written_to_the_wrong_slot":
        page_written_to_the_wrong_slot(engine)
    live = serve_open.next_logits_of_live_slots(engine, loop, most=4)
    assert len(live) == 3 and all(len(seq) > 30 for seq, _ in live)
    cast = "float8_e4m3fn" if fault == "fp8_reference" else None
    checks = serve_open_arch.compare_with_reference(
        ref, flat, cell["sizes"], cell["mix"]["check"], [], live, 128, 40,
        control_cast=cast)
    (logits,) = checks
    assert logits["name"] == "live_logits_rel"
    arch = serve_open_arch.architecture(cell)
    states = arch.live_state(engine, sorted(loop.live), 4)
    state, dtype = arch.state_checks(
        flat, cell["sizes"], cell["mix"]["check"]["limits"],
        [(seq, got) for (seq, _), got in zip(live, states)], 128,
        control_cast=cast)
    assert (state["name"], dtype["name"]) == ("ssm_state_rel",
                                              "state_dtype_differs")
    assert dtype["ok"] == (fault != "bfloat16_state")
    if fault is None:
        assert logits["ok"] and logits["value"] < 2e-5, logits
        assert state["ok"] and state["value"] < 2e-5, state
    elif fault == "page_written_to_the_wrong_slot":
        # the pages are not the state's: only the logits can tell
        assert logits["value"] > 10 * logits["limit"], logits
        assert state["ok"]
    else:
        assert state["value"] > 10 * state["limit"], state
        assert logits["value"] > 10 * logits["limit"] or \
            fault == "bfloat16_state", logits


def test_an_older_program_refuses_the_architecture_cleanly(tiny,
                                                           monkeypatch):
    """The parent commit has no `models/falcon_h1.py`: the builder
    says so with exit code 2 at once."""
    import sys
    from benchmark.kinds import serve_open_arch
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_falcon_h1.CELL)
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.models.falcon_h1", None)
    with pytest.raises(SystemExit) as refused:
        serve_open_arch.build_engine(cell, SEED)
    assert refused.value.code == 2


def test_weights_are_seeded_and_the_heads_remember():
    import jax.numpy as jnp
    from benchmark import weights_falcon_h1 as weights
    sizes = tiny_falcon_h1.TINY_SIZES
    flat = weights.make_weights(sizes, SEED, jnp.bfloat16)
    again = weights.make_weights(sizes, SEED, jnp.bfloat16,
                                 only=("h.w_in", "head", "h.dt_bias"))
    assert all(np.array_equal(flat[k], again[k]) for k in again)
    other = weights.make_weights(sizes, SEED + 1, jnp.bfloat16)
    assert not np.array_equal(flat["h.wq"], other["h.wq"])
    assert flat["head"].shape == (64, 512) and \
        flat["h.w_in"].shape == (2, 64, 64 + 64 + 32 + 32 + 8) and \
        flat["h.conv_w"].shape == (2, 128, 4)
    # the scalars of the recurrence stay float32 whatever the cell's type
    assert {str(flat[k].dtype) for k in weights.FLOAT32_LEAVES} == \
        {"float32"} and str(flat["h.wq"].dtype) == "bfloat16"
    assert np.array_equal(flat["h.D"], np.ones((2, 8), np.float32))
    A = np.exp(np.asarray(flat["h.A_log"]))
    assert 1.0 <= A.min() and A.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(flat["h.dt_bias"])))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    # the multipliers are divided out of the spreads: the key
    # projection is 1 / key_multiplier wider than the query's
    ratio = float(np.std(np.asarray(flat["h.wk"], np.float32)) /
                  np.std(np.asarray(flat["h.wq"], np.float32)))
    assert ratio == pytest.approx(1 / sizes["key_multiplier"], rel=0.1)
    memory = np.asarray(weights.memory_lengths(sizes, SEED))
    assert memory.shape == (2, 8) and memory.min() > 0.5 and \
        memory.max() > 20 * memory.min()
    tree = weights.to_program_tree(flat)
    assert set(tree) == {"embed", "head", "norm_f", "layers"} and \
        tree["layers"]["wq"] is flat["h.wq"]


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
def test_regions_are_the_programs_vocabulary():
    from deepspeed_tpu.inference import engine
    from deepspeed_tpu.utils import scopes
    assert region_join.PAGED_STATE == engine.SCOPES_PAGED_STATE == \
        scopes.SCOPES_PAGED_STATE
    assert region_join.SSM == scopes.SCOPES_SSM == (
        scopes.SCOPE_STATE_RESET, scopes.SCOPE_SSM_CONV,
        scopes.SCOPE_SSM_CHUNK, scopes.SCOPE_STATE_UPDATE)
    # what the paged programs name keeps its name, and so does what
    # the recurrent ones name but for the chunked form's own region
    assert set(scope_reduce.REGIONS) <= set(region_join.PAGED_STATE)
    assert set(state_scopes.REGIONS) - set(region_join.PAGED_STATE) == \
        {"retention_chunk"}


L = "jit(decode_fn)/layers/while/body/closed_call/"
P = "jit(prefill_fn)/layers/while/body/closed_call/"
MAPS = {
    "jit_decode_fn": {"fusion.1": "jit(decode_fn)/embed/gather",
                      "while.1": "jit(decode_fn)/layers/while",
                      "fusion.2": L + "attn_qkv/dot_general",
                      "fusion.3": L + "ssm_conv/add",
                      "fusion.4": L + "state_update/mul",
                      "kernel.1": L + "attn/paged_decode_attention",
                      "fusion.5": L + "mlp/dot_general"},
    "jit_prefill_fn": {"while.2": "jit(prefill_fn)/layers/while",
                       "fusion.6": P + "state_reset/select_n",
                       "fusion.7": P + "ssm_chunk/while/body/dot_general",
                       "fusion.8": P + "kv_gather/gather",
                       "fusion.9": P + "attn/dot_general",
                       "fusion.10": P + "ssm_conv/add"},
}
op = lambda name, s, e: [f"%{name} = f32[8]{{0}} fusion(%p), kind=kLoop", s, e]
PLANES = {
    "/device:TPU:0": {
        "XLA Modules": [["jit_decode_fn(1)", 0.00, 0.10],
                        ["jit_decode_fn(1)", 0.10, 0.20],
                        ["jit_prefill_fn(2)", 0.20, 0.35]],
        "XLA Ops": [
            op("fusion.1", 0.00, 0.01), op("while.1", 0.01, 0.10),
            op("fusion.2", 0.01, 0.03), op("fusion.3", 0.03, 0.04),
            op("fusion.4", 0.04, 0.06), op("kernel.1", 0.06, 0.07),
            op("copy.77", 0.07, 0.08), op("fusion.5", 0.08, 0.10),
            op("fusion.1", 0.10, 0.11), op("while.1", 0.11, 0.20),
            op("fusion.4", 0.11, 0.14), op("kernel.1", 0.14, 0.15),
            op("while.2", 0.20, 0.35), op("fusion.6", 0.20, 0.21),
            op("fusion.10", 0.21, 0.22), op("fusion.7", 0.22, 0.27),
            op("fusion.8", 0.27, 0.29), op("fusion.9", 0.29, 0.35)]},
    "/host:CPU": {"main": [["bench/window", 0.0, 0.4]]},
}
# read off PLANES by hand
UPDATE, CHUNK, CONV, RESET, WINDOW = 0.02 + 0.03, 0.05, 0.01 + 0.01, 0.01, 0.4


@pytest.fixture()
def traced(monkeypatch):
    """ctx with the hand-made trace (two decode launches and a prefill
    launch), the registry holding its maps."""
    from test_scope_metrics import FakeCompiled
    monkeypatch.setattr(programs, "_programs", {})
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    for name, scopes in MAPS.items():
        programs.register(name, FakeCompiled(scopes))
    planes = {p: {l: [tuple(s) for s in spans] for l, spans in lines.items()}
              for p, lines in PLANES.items()}
    sizes = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "falcon-h1-34b.json")))
    mix = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "serve-longctx-steady.json")))
    return {"trace": trace_reduce.from_planes(planes),
            "cell": {"sizes": sizes, "mix": mix},
            "device": {"kind": "TPU v5 lite"}}


def test_region_seconds_by_hand(traced):
    secs = region_join.region_seconds(traced["trace"],
                                      region_join.PAGED_STATE,
                                      region_join.SSM)
    assert secs["state_update"] == pytest.approx(UPDATE)
    assert secs["ssm_chunk"] == pytest.approx(CHUNK)
    assert secs["ssm_conv"] == pytest.approx(CONV)
    assert secs["state_reset"] == pytest.approx(RESET)
    assert secs["attn"] == pytest.approx(0.01 + 0.01 + 0.06)
    assert secs["kv_gather"] == pytest.approx(0.02)
    # the copy the compiler put into the loop belongs to the loop, as
    # does the second launch's loop outside its fusions
    assert secs["layers"] == pytest.approx(0.01 + 0.05)
    assert sum(secs.values()) == pytest.approx(0.35)
    # another vocabulary, the same join: the recurrent programs' list
    # finds none of its state regions but `state_update`
    other = region_join.region_seconds(traced["trace"], state_scopes.REGIONS,
                                       ("retention_chunk",))
    assert other is None


def test_every_reader_returns_a_number_on_a_trace_with_both_launches(traced):
    slots = traced["cell"]["mix"]["inference"]["max_slots"]
    sizes = traced["cell"]["sizes"]
    per_launch = 2 * 6 * slots * 32 * 128 * 256 * 4
    assert ssm_costs.decode_state_traffic_bytes(sizes, slots) == per_launch
    flops, nbytes = ssm_costs.prefill_chunk_cost(sizes, 512, 128)
    want = {
        "ssm_state_time_share.serve":
            100 * (UPDATE + CHUNK + CONV + RESET) / WINDOW,
        "ssm_decode_roofline": 100 * 2 * per_launch / 819e9 / UPDATE,
        # memory is the longer bound at these sizes
        "ssm_prefill_roofline":
            100 * max(flops / 197e12, nbytes / 819e9) / CHUNK,
    }
    assert nbytes / 819e9 > flops / 197e12
    bench = harness.load_benchmark()
    listed = [m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)
              if m["source"] == "device_trace"]
    assert set(LISTED) <= set(listed)
    for name in listed + ["ssm_prefill_roofline", "prefill_chunk_ms"]:
        value = harness.read_metric(name, traced)
        assert value is not None and np.isfinite(value), name
        if name in want:
            assert value == pytest.approx(want[name]), name
    assert harness.read_metric("attention_time_share.serve", traced) == \
        pytest.approx(100 * 0.08 / WINDOW)
    assert harness.read_metric("kv_gather_time_share.serve", traced) == \
        pytest.approx(100 * 0.02 / WINDOW)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_another_models_run(name, traced,
                                                    monkeypatch):
    """GPT-2's and Brumby's programs (and the parent commit's) have no
    state-space regions, and a run without a trace has nothing to
    join: None, never 0 and never an error."""
    from test_scope_metrics import FakeCompiled
    others = {"jit_decode_fn": {
        "fusion.3": "jit(decode_fn)/layers/attn/x",
        "fusion.4": "jit(decode_fn)/layers/retention_chunk/x"}}
    monkeypatch.setattr(programs, "_programs", {})
    for program, scopes in others.items():
        programs.register(program, FakeCompiled(scopes))
    assert harness.read_metric(name, traced) is None
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    assert harness.read_metric(name, dict(traced, trace=None)) is None
    monkeypatch.setattr(programs, "_programs", {})
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    assert harness.read_metric(name, traced) is None


def test_cost_functions_against_hand_counts():
    sizes = {"num_hidden_layers": 2, "mamba_n_heads": 4, "mamba_d_head": 3,
             "mamba_d_state": 5, "mamba_n_groups": 2}
    # 2 layers x 3 slots x 4 heads x 3 x 5 values x 4 bytes
    assert ssm_costs.state_bytes(sizes, 3) == 2 * 3 * 4 * 3 * 5 * 4
    assert ssm_costs.decode_state_traffic_bytes(sizes, 3) == 2 * 1440
    flops, nbytes = ssm_costs.prefill_chunk_cost(sizes, 6, 4)
    half = 4 * 5 // 2                               # two chunks of <= 4
    pairs = 2 * (2 * half * 5 + 4 * half * 3) * 2
    state = 2 * 2 * 4 * 6 * 3 * 5
    assert flops == 2 * (pairs + state)
    assert nbytes == 2 * (2 * 4 * 3 * 5 * 4) + 2 * 6 * (2 * 12 + 2 * 10) * 2
    full = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "falcon-h1-34b.json")))
    # 16 slots x 6 layers x 4.19 MB: 0.403 GB resident, as ISSUE 31 reckons
    assert ssm_costs.state_bytes(full, 16) == 402_653_184


# ----------------------------------------------------------------------
# the files
# ----------------------------------------------------------------------
def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Falcon-H1-34B-Instruct"]
    return row


def test_configuration_keeps_every_published_value():
    """Every key of the catalog's row for the source at its published
    value, but for the depth, which `reduced` names; no width
    changed."""
    row = catalog_row()
    bench = harness.load_benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == "falcon-h1-34b"]
    with open(os.path.join(REPO, entry["file"])) as f:
        sizes = json.load(f)
    assert sizes["source"] == entry["source"] == row["source_url"]
    assert sizes["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    differs = [k for k, v in row["config"].items() if sizes[k] != v]
    assert differs == ["num_hidden_layers"] and sizes[differs[0]] == 6
    assert sizes["published"] == {"num_hidden_layers": 72}
    assert sizes["program"] == {"architecture": "falcon_h1",
                                "param_dtype": "bfloat16"}
    assert set(sizes["assumed"]) >= {
        "A_log", "dt_bias", "D", "conv", "gated_norm", "dt_clamp",
        "ssm_state", "ssm_state_dtype", "weights", "initializer_range"}
    assert "twelve pipeline stages" in sizes["deployment"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_the_programs_config_holds_the_published_values():
    """`FalconH1Config()`'s defaults are the row's values, key for
    key, where it has the key."""
    import dataclasses
    from deepspeed_tpu.models.falcon_h1 import FalconH1Config
    row = catalog_row()["config"]
    mine = dataclasses.asdict(FalconH1Config())
    shared = set(mine) & set(row)
    assert len(shared) >= 25
    for key in shared:
        want = tuple(row[key]) if isinstance(row[key], list) else row[key]
        assert mine[key] == want, key


def test_the_cell_and_its_metrics_are_appended():
    bench = harness.load_benchmark()
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    assert len(bench["workloads"][-1]["why"]) <= 200
    assert bench["configs"][-1]["name"] == "falcon-h1-34b"
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(LISTED)
    for m in bench["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
    for name in NEW:
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", name + ".py"))
    mine = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert mine >= {"itl_mean_ms", "serve_tokens_per_s", "setup_s",
                    "decode_iter_ms", "program_temp_gb.serve",
                    "device_idle_share.serve", "attention_time_share.serve",
                    "kv_gather_time_share.serve",
                    "weight_matmul_time_share.serve"}
    # `layers` alone would count the state's regions; the counter is
    # fed for a recurrent cache only; the retention readers' regions
    # do not exist in this model's programs
    # a traced tail need not hold a prefill launch (PERF.md section 7)
    assert not mine & {"prefill_chunk_ms", "ssm_prefill_roofline"}
    assert not mine & {"kv_pool_carry_time_share.serve",
                       "state_resident_gb.serve",
                       "retention_state_time_share.serve",
                       "retention_decode_roofline",
                       "retention_prefill_roofline"}
    # every metric the parent's cells listed still lists them, first
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL


def test_the_traffic_file_holds_the_issues_parameters():
    bench = harness.load_benchmark()
    loaded = harness.load_cell(bench, CELL)
    mix = loaded["mix"]
    assert mix["kind"] == "serve_open_arch" and mix["chips"] == 1
    inference = dict(mix["inference"])
    pool = inference.pop("kv_cache")
    assert inference == {"max_slots": 16, "prefill_chunk": 512,
                         "sync_every": 4, "max_new_tokens": 512,
                         "max_seq_len": 16384}
    # 196,608 tokens beside the scratch page
    assert (pool["num_pages"] - 1) * pool["page_size"] == 196608
    assert 128 % pool["page_size"] == 0 or pool["page_size"] % 128 == 0
    arrivals = mix["arrivals"]
    assert (arrivals["process"], arrivals["schedule_seed"],
            arrivals["seed_jitter_s"]) == ("jittered_grid", 31, 0.4)
    assert round(arrivals["rate_per_s"] / 0.05, 6) % 1 == 0
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                    "sigma": 0.5, "min": 2048, "max": 15360}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.5, "min": 64, "max": 512}
    assert mix["max_total_tokens"] == 16384 and mix["drain_s"] == 15
    assert mix["tokens"] == {"dist": "uniform"}
    assert (mix["check"]["requests"], mix["check"]["live_slots"]) == (3, 4)
    assert set(mix["check"]["limits"]) == {
        "live_logits_rel", "served_gap_max", "served_gap_mean",
        "ssm_state_rel"}
    assert mix["control"] == {"reference_cast": "float8_e4m3fn"}
    assert mix["control_program"] == {
        "model": {"ssm_state_dtype": "bfloat16"}}
    assert "sweep_knee_kind.py" in mix["sized_by"]
