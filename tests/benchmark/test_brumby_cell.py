"""What ISSUE 26 adds to the benchmark, driven on the CPU at a tiny
size (`tiny_brumby.py`): the kind `serve_open_arch` end to end; the
fp8 control, a bfloat16 state and a state dropped at a chunk boundary
each not correct; the state readers' region list against the
program's scopes and on a trace made by hand; the two cost functions
against hand counts."""

import json
import os
import time

import numpy as np
import pytest

import tiny_brumby
import tiny_copy
from benchmark import (harness, retention_costs, scope_reduce, state_scopes,
                       trace_reduce)
from deepspeed_tpu.monitor import programs

SEED = 2**31 + 77
REPO = tiny_copy.REPO
NEW = ("retention_state_time_share.serve", "retention_decode_roofline",
       "retention_prefill_roofline", "state_resident_gb.serve")


@pytest.fixture()
def tiny(tmp_path, monkeypatch):
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    monkeypatch.setattr(state_scopes, "_last", (None, None))
    return tiny_copy.point_harness_at(monkeypatch, tiny_brumby.make(tmp_path))


def run(h, **kw):
    return h.run_cell(tiny_brumby.CELL, SEED, 2.0, kw.pop("trace", 0),
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


def test_traced_run_reports_the_cache_managers_counter(tiny):
    """The CPU's profile has no device plane: the three device_trace
    readers have nothing to read and are left out; the counter and the
    existing serving readers are there."""
    result = run(tiny, trace=1)
    assert result["correct"]
    got = result["metrics"]
    # 4 slots x 2 layers x 2 heads x 36 rows x (8 + 1) float32
    assert got["state_resident_gb.serve"] == {
        "value": 4 * 2 * 2 * 36 * 9 * 4 / 1e9, "unit": "GB"}
    assert got["program_temp_gb.serve"]["value"] == pytest.approx(
        programs.memory("jit_decode_fn")["temp"] / 1e9)
    assert set(got) >= {"ttft_observed_mean_ms", "itl_p95_ms",
                        "slots_occupied_mean", "compiles_in_window.serve",
                        "peak_hbm_gb.serve"}
    assert not set(NEW[:3]) & set(got)


def drop_state_at_chunk_boundaries(monkeypatch):
    """Every prefill chunk starts from zero state: what a program that
    lost the state between chunks would compute."""
    from deepspeed_tpu.inference import engine as engine_mod
    real = engine_mod.retention_chunked

    def dropped(q, k, v, lg, S, z, *a, **kw):
        return real(q, k, v, lg, 0 * S, 0 * z, *a, **kw)
    monkeypatch.setattr(engine_mod, "retention_chunked", dropped)


@pytest.mark.parametrize("fault", [None, "fp8_reference", "bfloat16_state",
                                   "state_dropped_at_chunk_boundary"])
def test_live_slot_logits_against_the_reference(tiny, monkeypatch, fault):
    """Slots in mid-flight, prompts of several chunks behind them and
    tens of decode steps: sound float32 agrees with the reference to
    rounding; each fault lies at least 10 times past the limit (a
    bfloat16 state reads 19 times, the others hundreds)."""
    from benchmark.kinds import serve_open, serve_open_arch
    from deepspeed_tpu.inference import Request, ServingLoop
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_brumby.CELL)
    control = cell["mix"]["control_program"] \
        if fault == "bfloat16_state" else None
    if fault == "state_dropped_at_chunk_boundary":
        drop_state_at_chunk_boundaries(monkeypatch)
    engine, flat, ref = serve_open_arch.build_engine(cell, SEED, control)
    assert str(engine._state["state_s"].dtype) == (
        "bfloat16" if fault == "bfloat16_state" else "float32")
    loop = ServingLoop(engine)
    rng = np.random.default_rng(3)
    for i, n in enumerate((20, 45, 70)):
        loop.submit(Request(rid=i, tokens=rng.integers(0, 512, n),
                            max_new_tokens=40))
    loop._t0, loop._last_fence_t = time.monotonic(), 0.0
    for _ in range(12):
        loop.step()
    live = serve_open.next_logits_of_live_slots(engine, loop, most=4)
    assert len(live) == 3 and all(len(seq) > 30 for seq, _ in live)
    cast = "float8_e4m3fn" if fault == "fp8_reference" else None
    checks = serve_open_arch.compare_with_reference(
        ref, flat, cell["sizes"], cell["mix"]["check"], [], live, 128, 40,
        control_cast=cast)
    (check,) = checks
    assert check["name"] == "live_logits_rel"
    if fault is None:
        assert check["ok"] and check["value"] < 2e-5, check
    else:
        assert check["value"] > 10 * check["limit"], check
    # the state those slots are left with, against the all-pairs sum
    arch = serve_open_arch.architecture(cell)
    states = arch.live_state(engine, sorted(loop.live), 4)
    rows, dtype = arch.state_checks(
        flat, cell["sizes"], cell["mix"]["check"]["limits"],
        [(seq, got) for (seq, _), got in zip(live, states)], 128,
        control_cast=cast)
    assert (rows["name"], dtype["name"]) == ("state_rows_rel",
                                             "state_dtype_differs")
    assert dtype["ok"] == (fault != "bfloat16_state")
    if fault is None:
        assert rows["ok"] and rows["value"] < 1e-5, rows
    else:
        assert rows["value"] > 10 * rows["limit"], rows


def test_an_unknown_architecture_is_refused(tiny):
    from benchmark.kinds import serve_open_arch
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_brumby.CELL)
    cell["sizes"] = dict(cell["sizes"], program={"architecture": "other"})
    with pytest.raises(SystemExit):
        serve_open_arch.build_engine(cell, SEED)


def test_sweep_tool_takes_the_kind_and_applies_its_pre_roll_rule(
        tiny, monkeypatch, tmp_path, capsys):
    """`sweep_knee_kind.py` on the tiny cell: the engine from the
    mix's kind, a row a rate and pre-roll, and `rule:` = the lifetime
    at the answers' 90th percentile in the first row, to 5 s."""
    import sys
    from benchmark import sweep_knee_kind
    monkeypatch.setattr(tiny, "require_tpu", lambda chips: {"platform": "cpu"})
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(sys, "argv", [
        "sweep_knee_kind.py", "--workload", tiny_brumby.CELL, "--sweep",
        "1:4,8", "--sweep", "rule:8", "--seconds", "2", "--seed", str(SEED)])
    sweep_knee_kind.main()
    out = capsys.readouterr().out.splitlines()
    rows = [json.loads(line[6:]) for line in out if line.startswith("SWEEP ")]
    assert [(r["rate_per_s"], r["preroll_s"]) for r in rows] == [
        (4.0, 1.0), (8.0, 1.0), (8.0, 0.0)]      # lifetimes of ~40 ms -> 0 s
    assert all(r["sustained"] and r["failed"] == 0 for r in rows)
    first = rows[0]
    assert first["lifetime_at_q90_s"] == pytest.approx(1e-3 * (
        first["ttft_mean_ms"] + first["itl_mean_ms"] *
        first["answer_tokens_q90"]))
    assert json.loads(out[-1])["knee_per_s_by_preroll"] == {
        "1.0": 8.0, "0.0": 8.0}
    assert len(open(f"chiprun_out/sweep_{SEED}.jsonl").readlines()) == 3


def test_weights_are_seeded_and_the_gates_remember():
    import jax.numpy as jnp
    from benchmark import weights_brumby
    sizes = tiny_brumby.TINY_SIZES
    flat = weights_brumby.make_weights(sizes, SEED, jnp.float32)
    again = weights_brumby.make_weights(sizes, SEED, jnp.float32,
                                        only=("h.bg", "head"))
    assert all(np.array_equal(flat[k], again[k]) for k in again)
    other = weights_brumby.make_weights(sizes, SEED + 1, jnp.float32)
    assert not np.array_equal(flat["h.wq"], other["h.wq"])
    assert flat["head"].shape == (64, 512) and flat["embed"].shape == (512, 64)
    lo, hi = weights_brumby.GATE_BIAS
    assert lo <= float(flat["h.bg"].min()) and float(flat["h.bg"].max()) <= hi
    memory = np.asarray(weights_brumby.memory_lengths(sizes, SEED, 256))
    assert memory.shape == (2, 2) and memory.min() > 2.0
    tree = weights_brumby.to_program_tree(flat)
    assert set(tree) == {"embed", "head", "norm_f", "layers"} and \
        tree["layers"]["wq"] is flat["h.wq"]


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
def test_regions_are_the_programs_vocabulary():
    from deepspeed_tpu.inference import engine
    from deepspeed_tpu.utils import scopes
    assert state_scopes.REGIONS == engine.SCOPES_RECURRENT == \
        scopes.SCOPES_RECURRENT
    assert state_scopes.IN_LAYER == scopes.SCOPES_IN_LAYER_RECURRENT
    assert state_scopes.STATE == scopes.SCOPES_STATE == (
        scopes.SCOPE_STATE_RESET, scopes.SCOPE_RETENTION_CHUNK,
        scopes.SCOPE_STATE_UPDATE)
    # what both kinds of program share keeps one name
    assert set(scope_reduce.REGIONS) & set(state_scopes.REGIONS) == {
        "embed", "layers", "attn_qkv", "attn_out", "mlp", "head", "sample",
        "bookkeeping"}


L = "jit(decode_fn)/layers/while/body/closed_call/"
P = "jit(prefill_fn)/layers/while/body/closed_call/"
MAPS = {
    "jit_decode_fn": {"fusion.1": "jit(decode_fn)/embed/gather",
                      "while.1": "jit(decode_fn)/layers/while",
                      "fusion.2": L + "attn_qkv/dot_general",
                      "fusion.3": L + "state_update/add",
                      "fusion.4": L + "state_update/dot_general",
                      "fusion.5": L + "mlp/dot_general"},
    "jit_prefill_fn": {"while.2": "jit(prefill_fn)/layers/while",
                       "fusion.6": P + "state_reset/select_n",
                       "fusion.7": P + "retention_chunk/while/body/dot",
                       "fusion.8": P + "mlp/dot_general"},
}
op = lambda name, s, e: [f"%{name} = f32[8]{{0}} fusion(%p), kind=kLoop", s, e]
PLANES = {
    "/device:TPU:0": {
        "XLA Modules": [["jit_decode_fn(1)", 0.00, 0.10],
                        ["jit_decode_fn(1)", 0.10, 0.20],
                        ["jit_prefill_fn(2)", 0.20, 0.35]],
        "XLA Ops": [
            op("fusion.1", 0.00, 0.01), op("while.1", 0.01, 0.10),
            op("fusion.2", 0.01, 0.03), op("fusion.3", 0.03, 0.05),
            op("fusion.4", 0.05, 0.07), op("copy.77", 0.07, 0.08),
            op("fusion.5", 0.08, 0.10),
            op("fusion.1", 0.10, 0.11), op("while.1", 0.11, 0.20),
            op("fusion.3", 0.11, 0.14), op("fusion.4", 0.14, 0.15),
            op("while.2", 0.20, 0.35), op("fusion.6", 0.20, 0.21),
            op("fusion.7", 0.21, 0.31), op("fusion.8", 0.31, 0.35)]},
    "/host:CPU": {"main": [["bench/window", 0.0, 0.4]]},
}
# read off PLANES by hand: fusion.3 + fusion.4 twice; fusion.7; fusion.6
UPDATE, CHUNK, RESET, WINDOW = 0.04 + 0.04, 0.10, 0.01, 0.4


@pytest.fixture()
def traced(monkeypatch):
    """ctx with the hand-made trace, the registry holding its maps."""
    from test_scope_metrics import FakeCompiled
    monkeypatch.setattr(programs, "_programs", {})
    monkeypatch.setattr(state_scopes, "_last", (None, None))
    for name, scopes in MAPS.items():
        programs.register(name, FakeCompiled(scopes))
    planes = {p: {l: [tuple(s) for s in spans] for l, spans in lines.items()}
              for p, lines in PLANES.items()}
    sizes = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "brumby-14b.json")))
    mix = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "serve-longdoc-steady.json")))
    return {"trace": trace_reduce.from_planes(planes),
            "cell": {"sizes": sizes, "mix": mix},
            "device": {"kind": "TPU v5 lite"},
            "state_resident_bytes": 4_362_338_304}


def test_region_seconds_by_hand(traced):
    secs = state_scopes.region_seconds(traced["trace"])
    assert secs["state_update"] == pytest.approx(UPDATE)
    assert secs["retention_chunk"] == pytest.approx(CHUNK)
    assert secs["state_reset"] == pytest.approx(RESET)
    # the copy the compiler put into the loop belongs to the loop, as
    # does the second launch's loop outside its two fusions
    assert secs["layers"] == pytest.approx(0.01 + 0.05)
    assert secs["attn_qkv"] == pytest.approx(0.02)
    assert secs["mlp"] == pytest.approx(0.02 + 0.04)
    assert sum(secs.values()) == pytest.approx(0.35)


def test_readers_known_answers(traced):
    slots = traced["cell"]["mix"]["inference"]["max_slots"]
    sizes = traced["cell"]["sizes"]
    per_launch = 2 * 8 * slots * 8 * 8256 * 129 * 4
    assert retention_costs.decode_state_traffic_bytes(sizes, slots) == \
        per_launch
    want = {
        "retention_state_time_share.serve":
            100 * (UPDATE + CHUNK + RESET) / WINDOW,
        "retention_decode_roofline":
            100 * 2 * per_launch / 819e9 / UPDATE,
        "retention_prefill_roofline":
            100 * retention_costs.prefill_chunk_cost(sizes, 512, 128)[0]
            / 197e12 / CHUNK,
        "state_resident_gb.serve": 4.362338304,
    }
    for name in NEW:
        assert harness.read_metric(name, traced) == pytest.approx(want[name])


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_paged_models_run(name, traced,
                                                    monkeypatch):
    """GPT-2's programs (and the parent commit's) have no state
    regions, a run without a trace has nothing to join, and the GPT-2
    kind's ctx has no counter: None, never 0 and never an error."""
    paged = {"jit_decode_fn": {"fusion.3": "jit(decode_fn)/layers/attn/x"}}
    monkeypatch.setattr(programs, "_programs", {})
    from test_scope_metrics import FakeCompiled
    for program, scopes in paged.items():
        programs.register(program, FakeCompiled(scopes))
    ctx = {k: v for k, v in traced.items() if k != "state_resident_bytes"}
    assert harness.read_metric(name, ctx) is None
    monkeypatch.setattr(state_scopes, "_last", (None, None))
    assert harness.read_metric(name, dict(ctx, trace=None)) is None
    monkeypatch.setattr(programs, "_programs", {})
    monkeypatch.setattr(state_scopes, "_last", (None, None))
    assert harness.read_metric(name, ctx) is None


def test_cost_functions_against_hand_counts():
    sizes = {"num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 4}
    assert retention_costs.state_rows(sizes) == 10
    # 2 layers x 3 slots x 2 heads x 10 rows x (4 + 1) values x 4 bytes
    assert retention_costs.state_bytes(sizes, 3) == 2 * 3 * 2 * 10 * 5 * 4
    assert retention_costs.decode_state_traffic_bytes(sizes, 3) == 2 * 2400
    flops, nbytes = retention_costs.prefill_chunk_cost(sizes, 6, 4)
    pairs = 2 * 2 * 4 * (4 * 5 // 2) * 4 * 2        # two chunks of <= 4
    into, out = 2 * 2 * 6 * 10 * 5, 2 * 4 * 6 * 10 * 5
    phi = 2 * (4 + 2) * 6 * 10
    assert flops == 2 * (pairs + into + out + phi)
    assert nbytes == 2 * (2 * 1 * 2 * 10 * 5 * 4) + 2 * 6 * (8 + 4) * 4 * 2
    full = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "brumby-14b.json")))
    assert retention_costs.state_rows(full) == 8256
    # 16 slots x 8 layers: 4.36 GB resident, as ISSUE 26 reckons
    assert retention_costs.state_bytes(full, 16) == 4_362_338_304


# ----------------------------------------------------------------------
# the files
# ----------------------------------------------------------------------
def test_configuration_keeps_every_published_number():
    """Every number of the catalog's row for the source, under its
    key, but for the depth, which `reduced` names; no width changed."""
    published = {
        "head_dim": 128, "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "num_attention_heads": 40, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000, "vocab_size": 151936}
    bench = harness.load_benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == "brumby-14b"]
    with open(os.path.join(REPO, entry["file"])) as f:
        sizes = json.load(f)
    assert sizes["source"] == entry["source"] and len(entry["source"]) <= 200
    assert sizes["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    differs = [k for k, v in published.items() if sizes[k] != v]
    assert differs == ["num_hidden_layers"] and sizes[differs[0]] == 8
    assert sizes["published"] == {"num_hidden_layers": 40}
    assert sizes["attention_bias"] is False and \
        sizes["tie_word_embeddings"] is False and \
        sizes["model_type"] == "brumby" and sizes["hidden_act"] == "silu"
    assert sizes["program"] == {"architecture": "brumby",
                                "param_dtype": "bfloat16"}
    assert set(sizes["assumed"]) >= {"degree", "scale", "normaliser", "gate",
                                     "q_k_norm_and_rotary", "state",
                                     "retention"}


def test_the_cell_and_its_metrics_are_appended():
    bench = harness.load_benchmark()
    cell = "brumby-14b.serve-longdoc-steady"
    assert bench["workloads"][-1]["name"] == cell
    assert bench["workloads"][-1]["chips"] == 1
    assert len(bench["workloads"][-1]["why"]) <= 200
    assert bench["configs"][-1]["name"] == "brumby-14b"
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == [cell]
    mine = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if cell in m.get("workloads", [cell])}
    assert mine >= {"itl_mean_ms", "serve_tokens_per_s", "setup_s",
                    "decode_iter_ms", "prefill_chunk_ms",
                    "program_temp_gb.serve", "device_idle_share.serve"}
    # the regions these read do not exist in this model's programs
    assert not mine & {"kv_pool_carry_time_share.serve",
                       "kv_gather_time_share.serve",
                       "attention_time_share.serve"}
    loaded = harness.load_cell(bench, cell)
    assert loaded["mix"]["kind"] == "serve_open_arch"
    assert loaded["mix"]["inference"]["max_seq_len"] == \
        loaded["mix"]["max_total_tokens"] == 8192
