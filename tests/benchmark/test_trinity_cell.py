"""What ISSUE 35 adds to the benchmark, driven on the CPU at a tiny
size (`tiny_trinity.py`): the Trinity cell end to end through the kind
`serve_open_arch`; the fp8 reference, a dropped pick, a selection bias
left out and a window one key short each not correct; the new readers
on the program's own fence rows and on a trace made by hand with both
launches in it; the cost functions against hand counts; the files."""

import json
import os
import sys
import time

import numpy as np
import pytest

import tiny_copy
import tiny_trinity
from benchmark import (harness, moe_costs, region_join, scope_reduce,
                       state_scopes, trace_reduce)
from benchmark.architectures import afmoe
from deepspeed_tpu.monitor import programs

SEED = 2**31 + 77
REPO = tiny_copy.REPO
CELL = tiny_trinity.FULL_CELL
NEW = ("moe_time_share.serve", "moe_expert_roofline",
       "moe_experts_touched_share", "kv_window_resident_share.serve")
COUNTED = NEW[2:]                 # program counters: no device needed


def fences(rows):
    """A sink holding `rows` [(the loop's clock, fence row)] as the
    serving loop's `decode_batch` events."""
    sink = afmoe.FenceRows()
    for t, row in rows:
        sink.emit(dict(row, kind="decode_batch", loop_s=t))
    return sink


@pytest.fixture()
def tiny(tmp_path, monkeypatch):
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    monkeypatch.setattr(state_scopes, "_last", (None, None))
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    return tiny_copy.point_harness_at(monkeypatch,
                                      tiny_trinity.make(tmp_path))


def run(h, **kw):
    return h.run_cell(tiny_trinity.CELL, SEED, 2.0, kw.pop("trace", 0),
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
    have nothing to read and are left out; the two readers of the
    program's counters need no device and are there."""
    result = run(tiny, trace=1)
    assert result["correct"]
    got = result["metrics"]
    assert set(got) >= {"ttft_observed_mean_ms", "itl_p95_ms",
                        "slots_occupied_mean", "compiles_in_window.serve",
                        "peak_hbm_gb.serve", "queue_wait_mean_ms",
                        "program_temp_gb.serve"} | set(COUNTED)
    assert not set(NEW[:2]) & set(got)
    touched = got["moe_experts_touched_share"]["value"]
    resident = got["kv_window_resident_share.serve"]["value"]
    # 4 slots x 4 picks over 16 experts: some, never all of them every
    # launch; contexts pass the window of 24 several times over
    assert 25 < touched < 100 and 10 < resident < 100


# ----------------------------------------------------------------------
# faults, each read against the sound run's limits
# ----------------------------------------------------------------------
def drop_a_pick(monkeypatch):
    """Every token's last pick goes to the expert beside it at weight
    zero: what a program whose top-k kept one pick too few would
    compute (the routed sum loses its smallest share)."""
    from deepspeed_tpu.moe import serving as moe
    real = moe.route

    def dropped(x, w_router, expert_bias, top_k, route_scale):
        picks, weights, scores = real(x, w_router, expert_bias, top_k,
                                      route_scale)
        return (picks.at[:, -1].set((picks[:, -1] + 1) % scores.shape[-1]),
                weights.at[:, -1].set(0.0), scores)
    monkeypatch.setattr(moe, "route", dropped)


def leave_the_bias_out(monkeypatch):
    from deepspeed_tpu.moe import serving as moe
    real = moe.route
    monkeypatch.setattr(moe, "route", lambda x, w, bias, *a: real(
        x, w, 0 * bias, *a))


FAULTS = [None, "fp8_reference", "dropped_pick", "bias_left_out",
          "window_one_key_short"]


@pytest.mark.parametrize("fault", FAULTS)
def test_live_slots_against_the_reference(tiny, monkeypatch, fault):
    """Slots in mid-flight, prompts of several launches behind them
    and decode steps through both tables, every context past twice the
    window: sound float32 agrees with the reference to rounding on the
    logits and on every pick of every expert layer; each fault lies
    past a limit (the two of the router on the picks, the window on
    the logits)."""
    from benchmark.kinds import serve_open, serve_open_arch
    from deepspeed_tpu.inference import Request, ServingLoop
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_trinity.CELL)
    if fault == "dropped_pick":
        drop_a_pick(monkeypatch)
    if fault == "bias_left_out":
        leave_the_bias_out(monkeypatch)
    control = {"model": {"sliding_window": 23}} \
        if fault == "window_one_key_short" else None
    engine, flat, ref = serve_open_arch.build_engine(cell, SEED, control)
    arch = serve_open_arch.architecture(cell)
    assert arch is afmoe
    # as the kind does, on an empty engine: nothing launched, no picks
    assert arch.live_state(engine, [0], 4) == [{"picks": None}]
    loop = ServingLoop(engine)
    rng = np.random.default_rng(3)
    for i, (n, m) in enumerate([(70, 40), (30, 40), (85, 40), (50, 30)]):
        loop.submit(Request(rid=i, tokens=rng.integers(0, 512, n),
                            max_new_tokens=m))
    loop._t0, loop._last_fence_t = time.monotonic(), 0.0
    for _ in range(14):
        loop.step()
    assert engine.cache.window.released_pages() > 8
    live = serve_open.next_logits_of_live_slots(engine, loop, most=4)
    assert len(live) == 4 and all(len(seq) > 48 for seq, _ in live)
    cast = "float8_e4m3fn" if fault == "fp8_reference" else None
    (logits,) = serve_open_arch.compare_with_reference(
        ref, flat, cell["sizes"], cell["mix"]["check"], [], live, 128, 40,
        control_cast=cast)
    assert logits["name"] == "live_logits_rel"
    before = [id(x) for x in engine.cache_arrays()]
    states = arch.live_state(engine, sorted(loop.live), 4)
    assert all(s["picks"].shape == (4, 4) for s in states)
    # it reads what the launch gave out and touches nothing
    assert [id(x) for x in engine.cache_arrays()] == before
    assert len(arch.fence_rows({"cell": cell})) == 14
    (picks,) = arch.state_checks(
        flat, cell["sizes"], cell["mix"]["check"]["limits"],
        [(seq, got) for (seq, _), got in zip(live, states)], 128,
        control_cast=cast)
    assert picks["name"] == "router_picks_agree"
    if fault is None:
        assert logits["ok"] and logits["value"] < 2e-5, logits
        assert picks["ok"] and picks["value"] == 1.0, picks
        # the kind's launch advanced every live slot by a token that
        # the loop's count of positions does not hold; the engine
        # counts the launches since the fence in when it is asked for
        # pages (`ensure_decode_capacity`), so the loop goes on and
        # every request ends on the reference's tokens
        while loop.live or loop.prefilling or loop.queue:
            loop.step()
        sample = [(np.asarray(r.tokens), np.asarray(r.out_tokens))
                  for r in loop.results]
        gaps = serve_open_arch.compare_with_reference(
            ref, flat, cell["sizes"], cell["mix"]["check"], sample, [], 128,
            40)
        assert all(c["ok"] for c in gaps) and len(gaps) == 2, gaps
    elif fault == "window_one_key_short":
        # the picks ride on the logits' error and need not flip
        assert logits["value"] > 10 * logits["limit"], logits
    elif fault == "bias_left_out":
        # it decides a pick here and there: the picks say so first
        assert not picks["ok"] and picks["value"] < 0.99, picks
        assert logits["value"] > 10 * logits["limit"], logits
    else:
        assert not picks["ok"] and picks["value"] <= 0.9, picks
        assert logits["value"] > 10 * logits["limit"], logits


def test_an_older_program_refuses_the_architecture_cleanly(tiny,
                                                           monkeypatch):
    """The parent commit has no `models/trinity.py`: the builder says
    so with exit code 2 at once."""
    from benchmark.kinds import serve_open_arch
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_trinity.CELL)
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.models.trinity", None)
    with pytest.raises(SystemExit) as refused:
        serve_open_arch.build_engine(cell, SEED)
    assert refused.value.code == 2


def test_weights_are_seeded_and_every_path_shows():
    import jax.numpy as jnp
    from benchmark import weights_afmoe as weights
    sizes = tiny_trinity.TINY_SIZES
    flat = weights.make_weights(sizes, SEED, jnp.bfloat16)
    again = weights.make_weights(sizes, SEED, jnp.bfloat16,
                                 only=("h.w_gate", "head", "h.expert_bias"))
    assert all(np.array_equal(flat[k], again[k]) for k in again)
    other = weights.make_weights(sizes, SEED + 1, jnp.bfloat16)
    assert not np.array_equal(flat["h.wq"], other["h.wq"])
    assert flat["head"].shape == (64, 512) and \
        flat["embed"].shape == (512, 64) and \
        flat["h.w_gate"].shape == (4, 16, 64, 32) and \
        flat["h.w_down"].shape == (4, 16, 32, 64) and \
        flat["d.w_gate"].shape == (1, 64, 96) and \
        flat["h.router"].shape == (4, 64, 16) and \
        flat["h.wg"].shape == (4, 64, 48) and flat["h.wk"].shape == (4, 64, 16)
    assert str(flat["h.expert_bias"].dtype) == "float32" and \
        str(flat["h.wq"].dtype) == "bfloat16"
    # norm weights round 1, the bias small round 0: both paths show
    norm = np.asarray(flat["h.q_norm"], np.float32)
    assert 0.05 < norm.std() < 0.2 and abs(norm.mean() - 1) < 0.1
    bias = np.asarray(flat["h.expert_bias"])
    assert 0.005 < bias.std() < 0.05 and abs(bias.mean()) < 0.02
    # residual projections carry 1 / sqrt(2 x the PUBLISHED depth)
    std = lambda k: float(np.std(np.asarray(flat[k], np.float32)))
    assert std("h.wo") / std("h.wq") == pytest.approx(
        1 / np.sqrt(2 * sizes["published"]["num_hidden_layers"]), rel=0.1)
    tree = weights.to_program_tree(flat)
    assert set(tree) == {"embed", "head", "norm_f", "dense", "layers"} and \
        tree["layers"]["w_up"] is flat["h.w_up"] and \
        tree["dense"]["w_up"] is flat["d.w_up"]


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
def test_regions_are_the_programs_vocabulary():
    from deepspeed_tpu.inference import engine
    from deepspeed_tpu.utils import scopes
    assert moe_costs.PAGED_MOE == engine.SCOPES_PAGED_MOE == \
        scopes.SCOPES_PAGED_MOE
    assert moe_costs.MOE == scopes.SCOPES_MOE == (
        "moe_router", "moe_dispatch", "moe_experts", "moe_shared",
        "moe_combine")
    # what the paged programs name keeps its name
    assert set(scope_reduce.REGIONS) <= set(moe_costs.PAGED_MOE)


L = "jit(decode_fn)/layers/while/body/closed_call/"
P = "jit(prefill_fn)/layers/while/body/closed_call/"
MAPS = {
    "jit_decode_fn": {"fusion.1": "jit(decode_fn)/embed/gather",
                      "while.1": "jit(decode_fn)/layers/while",
                      "fusion.2": L + "attn_qkv/dot_general",
                      "kernel.1": L + "attn/paged_decode_attention",
                      "fusion.3": L + "mlp/moe_router/dot_general",
                      "fusion.4": L + "mlp/moe_dispatch/cumsum",
                      "kernel.2": L + "mlp/moe_experts/gmm",
                      "fusion.5": L + "mlp/moe_shared/dot_general",
                      "fusion.6": L + "mlp/moe_combine/reduce_sum",
                      "fusion.7": L + "mlp/mul"},
    "jit_prefill_fn": {"while.2": "jit(prefill_fn)/layers/while",
                       "fusion.8": P + "kv_gather/gather",
                       "fusion.9": P + "attn/dot_general",
                       "kernel.3": P + "mlp/moe_experts/gmm",
                       "fusion.10": P + "mlp/moe_combine/reduce_sum"},
}
op = lambda name, s, e: [f"%{name} = f32[8]{{0}} fusion(%p), kind=kLoop", s, e]
PLANES = {
    "/device:TPU:0": {
        "XLA Modules": [["jit_decode_fn(1)", 0.00, 0.10],
                        ["jit_decode_fn(1)", 0.10, 0.20],
                        ["jit_prefill_fn(2)", 0.20, 0.35]],
        "XLA Ops": [
            op("fusion.1", 0.00, 0.01), op("while.1", 0.01, 0.10),
            op("fusion.2", 0.01, 0.02), op("kernel.1", 0.02, 0.03),
            op("fusion.3", 0.03, 0.035), op("fusion.4", 0.035, 0.04),
            op("kernel.2", 0.04, 0.07), op("fusion.5", 0.07, 0.075),
            op("fusion.6", 0.075, 0.08), op("fusion.7", 0.08, 0.10),
            op("fusion.1", 0.10, 0.11), op("while.1", 0.11, 0.20),
            op("kernel.2", 0.11, 0.15), op("kernel.1", 0.15, 0.16),
            op("while.2", 0.20, 0.35), op("fusion.8", 0.20, 0.22),
            op("fusion.9", 0.22, 0.27), op("kernel.3", 0.27, 0.33),
            op("fusion.10", 0.33, 0.35)]},
    "/host:CPU": {"main": [["bench/window", 0.0, 0.4]]},
}
# read off PLANES by hand
EXPERTS = 0.03 + 0.04 + 0.06
MOE = EXPERTS + 0.005 * 4 + 0.02
WINDOW = 0.4
# the fence rows the program would have logged: a window of two
# fences, then the traced tail's twelve (two decode launches and one
# prefill launch each)
ROW = {"iterations": 2, "prefill_launches": 0, "moe_experts_touched": 900,
       "moe_rows": 2 * 4 * 96 * 8, "moe_rows_max_expert": 30,
       "prefill_moe_experts_touched": 0, "prefill_moe_rows": 0,
       "kv_pages_window_in_use": 300, "kv_pages_window_released": 100}
TAIL = dict(ROW, prefill_launches=1, moe_experts_touched=1000,
            prefill_moe_experts_touched=512,
            prefill_moe_rows=4 * 512 * 8)
FENCES = [(-3.0, dict(ROW, moe_experts_touched=7)), (0.5, ROW),
          (1.5, dict(ROW, moe_experts_touched=1024,
                     kv_pages_window_in_use=100,
                     kv_pages_window_released=300)),
          ] + [(70.0 + i, TAIL) for i in range(12)]


@pytest.fixture()
def traced(monkeypatch):
    """ctx with the hand-made trace (two decode launches and a prefill
    launch) and the fence rows, the registry holding the maps."""
    from test_scope_metrics import FakeCompiled
    monkeypatch.setattr(programs, "_programs", {})
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    monkeypatch.setattr(afmoe, "_fences", fences(FENCES))
    for name, scopes in MAPS.items():
        programs.register(name, FakeCompiled(scopes))
    planes = {p: {l: [tuple(s) for s in spans] for l, spans in lines.items()}
              for p, lines in PLANES.items()}
    sizes = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "trinity-mini.json")))
    mix = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "serve-reason-steady.json")))
    return {"trace": trace_reduce.from_planes(planes),
            "cell": {"sizes": sizes, "mix": mix}, "fences_in_window": 2,
            "device": {"kind": "TPU v5 lite"}}


def test_region_seconds_by_hand(traced):
    secs = region_join.region_seconds(traced["trace"], moe_costs.PAGED_MOE,
                                      moe_costs.MOE)
    assert secs["moe_experts"] == pytest.approx(EXPERTS)
    for region in ("moe_router", "moe_dispatch", "moe_shared"):
        assert secs[region] == pytest.approx(0.005)
    assert secs["moe_combine"] == pytest.approx(0.005 + 0.02)
    # the norms round the feed-forward stay under `mlp`
    assert secs["mlp"] == pytest.approx(0.02)
    assert secs["attn"] == pytest.approx(0.01 + 0.01 + 0.05)
    assert sum(secs.values()) == pytest.approx(0.35)


def test_every_reader_returns_a_number_on_a_trace_with_both_launches(traced):
    sizes = traced["cell"]["sizes"]
    expert = 3 * 2048 * 1024 * 2
    assert moe_costs.expert_bytes(sizes) == expert
    # the tail's row: the experts its launches touched, rows in and out
    nbytes = (1000 + 512) * expert + \
        2 * (2 * 4 * 96 * 8 + 4 * 512 * 8) * 2048 * 2
    want = {
        "moe_time_share.serve": 100 * MOE / WINDOW,
        "moe_expert_roofline": 100 * nbytes / 819e9 / EXPERTS,
        # the window's two rows: (900 + 1024) of 2 x 2 launches x 512
        "moe_experts_touched_share": 100 * 1924 / (4 * 512),
        "kv_window_resident_share.serve": 100 * (0.75 + 0.25) / 2,
    }
    bench = harness.load_benchmark()
    listed = [m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)
              if m["source"] == "device_trace" or m["name"] in NEW]
    assert set(NEW) <= set(listed)
    for name in listed:
        value = harness.read_metric(name, traced)
        assert value is not None and np.isfinite(value), name
        if name in want:
            assert value == pytest.approx(want[name]), name
    assert harness.read_metric("attention_time_share.serve", traced) == \
        pytest.approx(100 * 0.07 / WINDOW)
    assert harness.read_metric("kv_gather_time_share.serve", traced) == \
        pytest.approx(100 * 0.02 / WINDOW)


def test_the_roofline_reads_the_tails_launches_whatever_the_rows_hold(
        traced, monkeypatch):
    """The trace's own launches are what is counted: a tail whose rows
    hold another number of launches than the trace (a launch cut by
    the window's edge) is scaled to the trace's, not dropped."""
    expert = 3 * 2048 * 1024 * 2
    rows = FENCES[:-12] + [(70.0 + i, dict(
        TAIL, iterations=4, moe_experts_touched=2000,
        moe_rows=4 * 4 * 96 * 8)) for i in range(12)]
    monkeypatch.setattr(afmoe, "_fences", fences(rows))
    nbytes = (1000 + 512) * expert + \
        2 * (2 * 4 * 96 * 8 + 4 * 512 * 8) * 2048 * 2
    assert harness.read_metric("moe_expert_roofline", traced) == \
        pytest.approx(100 * nbytes / 819e9 / EXPERTS)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_another_models_run(name, traced,
                                                    monkeypatch):
    """GPT-2's, Brumby's and Falcon-H1's programs (and the parent
    commit's) have no expert regions and log no such rows: None, never
    0 and never an error."""
    from test_scope_metrics import FakeCompiled
    others = {"jit_decode_fn": {
        "fusion.3": "jit(decode_fn)/layers/attn/x",
        "fusion.4": "jit(decode_fn)/layers/mlp/x"}}
    monkeypatch.setattr(programs, "_programs", {})
    for program, scopes in others.items():
        programs.register(program, FakeCompiled(scopes))
    other = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "falcon-h1-34b.json")))
    elsewhere = dict(traced, cell=dict(traced["cell"], sizes=other))
    assert harness.read_metric(name, elsewhere) is None
    # this architecture's cell on a program that counts nothing (no
    # rows), and a run without a trace
    monkeypatch.setattr(afmoe, "_fences", fences([]))
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    assert harness.read_metric(name, dict(traced, trace=None)) is None
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    assert harness.read_metric(name, traced) is None
    # rows of a program before the counters (no such keys)
    monkeypatch.setattr(afmoe, "_fences", fences([(0.5, {"iterations": 4})]))
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    assert harness.read_metric(name, traced) is None


def test_cost_functions_against_hand_counts():
    sizes = {"hidden_size": 6, "moe_intermediate_size": 5,
             "num_hidden_layers": 7, "num_dense_layers": 2}
    assert moe_costs.expert_bytes(sizes) == 3 * 6 * 5 * 2
    assert moe_costs.expert_bytes(sizes, 4) == 3 * 6 * 5 * 4
    assert moe_costs.expert_layers(sizes) == 5
    # 11 experts read once, 9 rows in and 9 out at width 6
    assert moe_costs.experts_traffic_bytes(sizes, 11, 9) == \
        11 * 180 + 2 * 9 * 6 * 2
    full = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "trinity-mini.json")))
    # 4 x 128 experts of 12.58 MB: the 6.44 GB a step that ISSUE 35 reckons
    assert moe_costs.expert_layers(full) * full["num_experts"] * \
        moe_costs.expert_bytes(full) == 6_442_450_944


# ----------------------------------------------------------------------
# the files
# ----------------------------------------------------------------------
def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Trinity-Mini"]
    return row


def test_configuration_keeps_every_published_value():
    """Every key of the catalog's row for the source at its published
    value, but for the two depths, which `reduced` names; no width
    changed."""
    row = catalog_row()
    bench = harness.load_benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == "trinity-mini"]
    with open(os.path.join(REPO, entry["file"])) as f:
        sizes = json.load(f)
    assert sizes["source"] == entry["source"] == row["source_url"]
    assert sizes["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers"]
    differs = {k for k, v in row["config"].items() if sizes[k] != v}
    assert differs == set(sizes["reduced"])
    assert (sizes["num_hidden_layers"], sizes["num_dense_layers"]) == (5, 1)
    assert sizes["published"] == {"num_hidden_layers": 32,
                                  "num_dense_layers": 2}
    # the layers held: one dense layer and one whole period
    types = [sizes["layer_types"][i] for i in sizes["kept_layers"]]
    assert sizes["kept_layers"] == [1, 4, 5, 6, 7] and \
        types == ["sliding_attention"] * 4 + ["full_attention"]
    assert (sizes["num_experts"], sizes["num_experts_per_tok"],
            sizes["vocab_size"]) == (128, 8, 200192)
    assert sizes["program"] == {"architecture": "afmoe",
                                "param_dtype": "bfloat16"}
    assert set(sizes["assumed"]) >= {
        "embedding_scale", "sandwich_norms", "qk_norm", "attention_gate",
        "rope", "window", "router", "expert_bias", "experts", "weights",
        "initializer_range", "dtype"}
    assert "pipeline stages" in sizes["deployment"] and \
        "ALL 128 experts" in sizes["deployment"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_the_programs_config_holds_the_published_values():
    """`TrinityConfig()`'s defaults are the row's values, key for key,
    where it has the key."""
    import dataclasses
    from deepspeed_tpu.models.trinity import TrinityConfig
    row = catalog_row()["config"]
    mine = dataclasses.asdict(TrinityConfig())
    shared = set(mine) & set(row)
    assert len(shared) >= 18
    for key in shared:
        want = tuple(row[key]) if isinstance(row[key], list) else row[key]
        assert mine[key] == want, key


# what the benchmark held when this cell was appended (PR 32's), in its
# order: the entries of this PR follow THESE, whatever later PRs append
PARENT_CELLS = ["gpt2-1.5b.train-zero2", "gpt2-1.5b.serve-chat-steady",
                "gpt2-350m.train-seq1024", "brumby-14b.serve-longdoc-steady",
                "falcon-h1-34b.serve-longctx-steady"]
PARENT_CONFIGS = ["gpt2-1.5b", "gpt2-350m", "brumby-14b", "falcon-h1-34b"]
PARENT_PER_LAYER = 30


def test_the_cell_and_its_metrics_are_appended():
    bench = harness.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells[:len(PARENT_CELLS) + 1] == PARENT_CELLS + [CELL]
    assert configs[:len(PARENT_CONFIGS) + 1] == \
        PARENT_CONFIGS + ["trinity-mini"]
    cell = bench["workloads"][len(PARENT_CELLS)]
    assert cell["chips"] == 1 and 20 < len(cell["why"]) <= 200
    mine = bench["per_layer"][PARENT_PER_LAYER:PARENT_PER_LAYER + 4]
    assert [m["name"] for m in mine] == list(NEW)
    assert not set(NEW) & {m["name"]
                           for m in bench["per_layer"][:PARENT_PER_LAYER]}
    for m in mine:
        assert m["workloads"][0] == CELL and m["unit"] == "%"
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".py"))
    layers = {m["name"]: (m["layer"], m["moves"], m["source"])
              for m in mine}
    assert layers == {
        NEW[0]: ("model", "itl_mean_ms", "device_trace"),
        NEW[1]: ("kernels (moe)", "itl_mean_ms", "device_trace"),
        NEW[2]: ("model", "itl_mean_ms", "program_counter"),
        NEW[3]: ("state layout", "serve_tokens_per_s", "program_counter")}
    mine = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert mine >= {"itl_mean_ms", "serve_tokens_per_s", "setup_s",
                    "decode_iter_ms", "program_temp_gb.serve",
                    "device_idle_share.serve", "attention_time_share.serve",
                    "kv_gather_time_share.serve", "peak_hbm_gb.serve",
                    "weight_matmul_time_share.serve",
                    "unscoped_time_share.serve", "slots_occupied_mean",
                    # four pools ride the layer scan's carry and pass a
                    # `lax.cond` a layer: a pool-sized copy would show
                    # under `layers` alone, which is what this reads
                    "kv_pool_carry_time_share.serve",
                    # a replay of the schedule over 200 seeds holds 5 to
                    # 12 prefill launches in every traced tail (PERF.md)
                    "prefill_chunk_ms"}
    # the counter is fed for a recurrent cache only; the other models'
    # regions do not exist in this model's programs
    assert not mine & {"state_resident_gb.serve",
                       "retention_state_time_share.serve",
                       "retention_decode_roofline",
                       "retention_prefill_roofline",
                       "ssm_state_time_share.serve", "ssm_decode_roofline"}
    # every metric the parent's cells listed still lists them, first
    # and in the parent's order; this cell follows them
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            before = m["workloads"][:m["workloads"].index(CELL)]
            assert before == [c for c in PARENT_CELLS if c in before], \
                m["name"]


def test_the_traffic_file_holds_the_issues_parameters():
    bench = harness.load_benchmark()
    mix = harness.load_cell(bench, CELL)["mix"]
    assert mix["kind"] == "serve_open_arch" and mix["chips"] == 1
    inference = dict(mix["inference"])
    pool = inference.pop("kv_cache")
    assert inference == {"max_slots": 96, "prefill_chunk": 512,
                         "sync_every": 4, "max_new_tokens": 2048,
                         "max_seq_len": 6144}
    assert pool == {"num_pages": 4097, "page_size": 128}
    arrivals = mix["arrivals"]
    assert (arrivals["process"], arrivals["schedule_seed"],
            arrivals["seed_jitter_s"]) == ("jittered_grid", 35, 0.4)
    assert round(arrivals["rate_per_s"] / 0.05, 6) % 1 == 0
    assert arrivals["preroll_s"] % 5 == 0
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1536,
                                    "sigma": 0.6, "min": 256, "max": 4096}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.5, "min": 384, "max": 2048}
    assert mix["max_total_tokens"] == 6144 and mix["drain_s"] == 15
    assert mix["tokens"] == {"dist": "uniform"}
    assert (mix["check"]["requests"], mix["check"]["live_slots"]) == (3, 4)
    assert set(mix["check"]["limits"]) == {
        "live_logits_rel", "served_gap_max", "served_gap_mean",
        "router_picks_agree"}
    assert 0.5 < mix["check"]["limits"]["router_picks_agree"] < 1
    assert "my chip runs, PR 35" in mix["check"]["limits_set_from"]
    assert mix["control"] == {"reference_cast": "float8_e4m3fn"}
    assert "sweep_knee_kind.py" in mix["sized_by"]
