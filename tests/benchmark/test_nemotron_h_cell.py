"""What ISSUE 45 adds to the benchmark, driven on the CPU at a tiny
size (`tiny_nemotron_h.py`): the Nemotron cell end to end through the
kind `serve_open_arch`; the fp8 reference, a bfloat16 state, a state
not reset at a slot's reuse, a K/V page written to the wrong slot, a
dropped pick and a selection bias left out each past a limit; the new
readers on the program's own fence rows and on a trace made by hand
with both launches in it; the cost functions against hand counts; the
files."""

import json
import os
import sys
import time

import numpy as np
import pytest

import tiny_copy
import tiny_nemotron_h
from benchmark import (harness, nemotron_h_costs, region_join, scope_reduce,
                       state_scopes, trace_reduce, traffic)
from benchmark.architectures import nemotron_h as arch_mod
from deepspeed_tpu.monitor import programs
from test_falcon_h1_cell import page_written_to_the_wrong_slot
from test_trinity_cell import drop_a_pick, fences, leave_the_bias_out

SEED = 2**31 + 45
REPO = tiny_copy.REPO
CELL = tiny_nemotron_h.FULL_CELL
NEW = ("nemotron_h_moe_held_roofline", "nemotron_h_moe_held_touched_share",
       "nemotron_h_ssm_decode_roofline")
COUNTED = NEW[1:2]                # a program counter: no device needed


@pytest.fixture()
def tiny(tmp_path, monkeypatch):
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    monkeypatch.setattr(state_scopes, "_last", (None, None))
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    return tiny_copy.point_harness_at(monkeypatch,
                                      tiny_nemotron_h.make(tmp_path))


def run(h, **kw):
    return h.run_cell(tiny_nemotron_h.CELL, SEED, 2.0, kw.pop("trace", 0),
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
    have nothing to read and are left out; the reader of the program's
    counter needs no device and is there."""
    result = run(tiny, trace=1)
    assert result["correct"]
    got = result["metrics"]
    assert set(got) >= {"ttft_observed_mean_ms", "itl_p95_ms",
                        "slots_occupied_mean", "compiles_in_window.serve",
                        "peak_hbm_gb.serve", "queue_wait_mean_ms",
                        "program_temp_gb.serve"} | set(COUNTED)
    assert not (set(NEW) - set(COUNTED)) & set(got)
    # a live slot's 4 picks of 16 over the 8 held x 7 layers: a launch
    # touches some of them, and with few slots live never all
    assert 5 < got[COUNTED[0]]["value"] < 100


# ----------------------------------------------------------------------
# faults, each read against the sound run's limits
# ----------------------------------------------------------------------
def keep_the_state_at_a_slots_reuse(monkeypatch):
    """The state half of every prefill launch is told the launch is
    not its request's first: a slot starts from what its last request
    left, as a program that never reset it would."""
    from deepspeed_tpu.inference import engine as engine_mod
    real = engine_mod.PagedStateKind.prefill_mixer

    def mixer(self, where, posv, valid, start, n_valid):
        paged, _ = real(self, where, posv, valid, start, n_valid)
        _, state = real(self, where, posv, valid, start + 1, n_valid)
        return paged, state
    monkeypatch.setattr(engine_mod.PagedStateKind, "prefill_mixer", mixer)


# fault -> the check that must be over its limit
FAULTS = {None: None, "fp8_reference": "ssm_state_rel",
          "bfloat16_state": "ssm_state_rel",
          "state_kept_at_reuse": "ssm_state_rel",
          "page_written_to_the_wrong_slot": "live_logits_rel",
          "dropped_pick": "router_picks_agree",
          "bias_left_out": "router_picks_agree"}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_live_slots_against_the_reference(tiny, monkeypatch, fault):
    """Slots in mid-flight that earlier requests have held and left,
    prompts of several launches behind them and decode steps through
    7 layers of state and 2 of pages: sound float32 agrees with the
    reference to rounding on the logits, on the first Mamba-2 layer's
    state element for element, and on every pick of every expert
    layer; each fault lies past a limit."""
    from benchmark.kinds import serve_open, serve_open_arch
    from deepspeed_tpu.inference import Request, ServingLoop
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_nemotron_h.CELL)
    broken = FAULTS[fault]
    control = cell["mix"]["control_program"] \
        if fault == "bfloat16_state" else None
    if fault == "dropped_pick":
        drop_a_pick(monkeypatch)
    if fault == "bias_left_out":
        leave_the_bias_out(monkeypatch)
    if fault == "state_kept_at_reuse":
        keep_the_state_at_a_slots_reuse(monkeypatch)
    engine, flat, ref = serve_open_arch.build_engine(cell, SEED, control)
    arch = serve_open_arch.architecture(cell)
    assert arch is arch_mod
    assert str(engine._state["ssm_state"].dtype) == (
        "bfloat16" if fault == "bfloat16_state" else "float32")
    # as the kind does, on an empty engine: nothing launched, no picks
    (empty,) = arch.live_state(engine, [0], 4)
    assert empty["picks"] is None and empty["H"].shape == (8, 8, 16)
    loop = ServingLoop(engine)
    rng = np.random.default_rng(3)
    # four short requests take the slots and leave them; four more
    # take them over
    for i, (n, m) in enumerate([(30, 3), (25, 3), (40, 3), (20, 3),
                                (70, 40), (30, 40), (85, 40), (50, 30)]):
        loop.submit(Request(rid=i, tokens=rng.integers(0, 512, n),
                            max_new_tokens=m))
    loop._t0, loop._last_fence_t = time.monotonic(), 0.0
    steps = 0
    while sorted(r.rid for r in loop.live.values()) != [4, 5, 6, 7]:
        loop.step()
        steps += 1
        assert steps < 40
    for _ in range(3):
        loop.step()
    steps += 3
    assert sorted(r.rid for r in loop.live.values()) == [4, 5, 6, 7]
    if fault == "page_written_to_the_wrong_slot":
        page_written_to_the_wrong_slot(engine)
    live = serve_open.next_logits_of_live_slots(engine, loop, most=4)
    assert len(live) == 4 and all(len(seq) > 30 for seq, _ in live)
    cast = "float8_e4m3fn" if fault == "fp8_reference" else None
    (logits,) = serve_open_arch.compare_with_reference(
        ref, flat, cell["sizes"], cell["mix"]["check"], [], live, 128, 40,
        control_cast=cast)
    assert logits["name"] == "live_logits_rel"
    before = [id(x) for x in engine.cache_arrays()]
    states = arch.live_state(engine, sorted(loop.live), 4)
    assert all(s["picks"].shape == (7, 4) for s in states)
    # it reads what the launch gave out and touches nothing
    assert [id(x) for x in engine.cache_arrays()] == before
    assert len(arch.fence_rows({"cell": cell})) == steps
    checks = {c["name"]: c for c in arch.state_checks(
        flat, cell["sizes"], cell["mix"]["check"]["limits"],
        [(seq, got) for (seq, _), got in zip(live, states)], 128,
        control_cast=cast)}
    assert set(checks) == {"ssm_state_rel", "state_dtype_differs",
                           "router_picks_agree"}
    checks["live_logits_rel"] = logits
    assert checks["state_dtype_differs"]["ok"] == (fault != "bfloat16_state")
    if fault is None:
        assert all(c["ok"] for c in checks.values()), checks
        assert logits["value"] < 2e-5 and \
            checks["ssm_state_rel"]["value"] < 2e-5 and \
            checks["router_picks_agree"]["value"] == 1.0, checks
        while loop.live or loop.prefilling or loop.queue:
            loop.step()
        sample = [(np.asarray(r.tokens), np.asarray(r.out_tokens))
                  for r in loop.results]
        gaps = serve_open_arch.compare_with_reference(
            ref, flat, cell["sizes"], cell["mix"]["check"], sample, [], 128,
            40)
        assert all(c["ok"] for c in gaps) and len(gaps) == 2, gaps
        return
    assert not checks[broken]["ok"], checks
    if broken == "router_picks_agree":
        assert checks[broken]["value"] <= 0.95, checks
    else:
        assert checks[broken]["value"] > 10 * checks[broken]["limit"], checks
    if fault == "page_written_to_the_wrong_slot":
        # the pages are not the state's: the first layer's state is sound
        assert checks["ssm_state_rel"]["ok"], checks


def test_an_older_program_refuses_the_architecture_cleanly(tiny,
                                                           monkeypatch):
    """The parent commit has no `models/nemotron_h.py`: the builder
    says so with exit code 2 at once."""
    from benchmark.kinds import serve_open_arch
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_nemotron_h.CELL)
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.models.nemotron_h", None)
    with pytest.raises(SystemExit) as refused:
        serve_open_arch.build_engine(cell, SEED)
    assert refused.value.code == 2


def test_weights_are_seeded_and_every_path_shows():
    import jax.numpy as jnp
    from benchmark import weights_nemotron_h as weights
    sizes = tiny_nemotron_h.TINY_SIZES
    flat = weights.make_weights(sizes, SEED, jnp.bfloat16)
    again = weights.make_weights(
        sizes, SEED, jnp.bfloat16,
        only=("x.w_up", "head", "r01.E.expert_bias", "r03.M.A_log"))
    assert len(again) == 4 and \
        all(np.array_equal(flat[k], again[k]) for k in again)
    other = weights.make_weights(sizes, SEED + 1, jnp.bfloat16)
    assert not np.array_equal(flat["r02.*.wq"], other["r02.*.wq"])
    # the share: 8 experts held and stored 32 wide with zeros past the
    # published 24, the router 16 wide, the slice's rows
    up, down = (np.asarray(flat[k], np.float32)
                for k in ("x.w_up", "x.w_down"))
    assert up.shape == (7, 8, 64, 32) and down.shape == (7, 8, 32, 64)
    assert not up[..., 24:].any() and not down[:, :, 24:].any()
    assert up[..., :24].all() and down[:, :, :24].all()
    assert flat["head"].shape == (64, 512) and \
        flat["r01.E.router"].shape == (2, 64, 16) and \
        flat["r01.E.shared_up"].shape == (2, 64, 48) and \
        flat["r03.M.w_in"].shape == (3, 64, 64 + 128 + 8) and \
        flat["r03.M.conv_w"].shape == (3, 128, 4) and \
        flat["r02.*.wk"].shape == (1, 64, 16) and \
        flat["r06.E.expert_bias"].shape == (1, 16)
    assert str(flat["r01.E.expert_bias"].dtype) == "float32" == \
        str(flat["r00.M.A_log"].dtype) and \
        str(flat["r00.M.w_in"].dtype) == "bfloat16"
    std = lambda x: float(np.std(np.asarray(x, np.float32)))
    # the residual projections carry 1 / sqrt(the PUBLISHED depth); the
    # dt segment of W_in an eighth of the others' spread
    assert std(flat["r03.M.w_out"]) / 0.02 == pytest.approx(
        1 / np.sqrt(52), rel=0.1)
    w_in = np.asarray(flat["r03.M.w_in"], np.float32)
    assert std(w_in[..., -8:]) / std(w_in[..., :64]) == pytest.approx(
        1 / 8, rel=0.15)
    A = np.exp(np.asarray(flat["r03.M.A_log"]))
    assert A.min() >= 1 and A.max() <= 16
    assert np.all(np.asarray(flat["r03.M.D"]) == 1)


@pytest.mark.parametrize("seed", [SEED, 5])
def test_the_balanced_bias_loads_the_experts_evenly(tiny, seed):
    """Under the drawn bias the 16 experts' loads on fresh uniform
    tokens differ by tens of a hundred; under `balanced_bias`, on rows
    it was not balanced on, the most loaded expert is within a quarter
    of the mean, the bias sums to zero a layer, and the same seed
    gives the same bias."""
    import jax
    import jax.numpy as jnp
    from benchmark import weights_nemotron_h as weights
    from benchmark.reference import nemotron_h as reference
    sizes = tiny.load_cell(tiny.load_benchmark(),
                           tiny_nemotron_h.CELL)["sizes"]
    flat = weights.make_weights(sizes, seed, jnp.float32)
    even = weights.balanced_bias(flat, sizes, seed, reference)
    assert sorted(even) == weights.bias_names(sizes)
    for name, bias in even.items():
        assert bias.shape == flat[name].shape and \
            str(bias.dtype) == "float32" and \
            np.abs(np.asarray(bias).sum(1)).max() < 1e-5
    again = weights.balanced_bias(flat, sizes, seed, reference)
    assert all(np.array_equal(even[k], again[k]) for k in even)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, sizes["vocab_size"], (8, 250)), jnp.int32)

    def worst(flat):
        """The most loaded expert's load over the mean, by layer."""
        picks = jax.vmap(lambda row: reference._through(
            flat, row, sizes, None)[1])(ids)
        return [np.bincount(np.asarray(p).ravel(), minlength=16).max() /
                (np.asarray(p).size / 16) for p in picks]
    assert max(worst(dict(flat, **even))) < 1.25 < min(worst(flat))


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
def test_regions_are_the_programs_vocabulary():
    from deepspeed_tpu.inference import layered_kind
    from deepspeed_tpu.utils import scopes
    assert nemotron_h_costs.LAYERED == layered_kind.SCOPES_LAYERED == \
        scopes.SCOPES_LAYERED
    assert nemotron_h_costs.MOE == scopes.SCOPES_MOE
    assert nemotron_h_costs.SSM == scopes.SCOPES_SSM == region_join.SSM
    # what the other families' programs name keeps its name
    assert set(region_join.PAGED_STATE) < set(nemotron_h_costs.LAYERED) > \
        set(scopes.SCOPES_PAGED_MOE)


L = "jit(decode_fn)/layers/while/body/closed_call/"
P = "jit(prefill_fn)/layers/while/body/closed_call/"
MAPS = {
    "jit_decode_fn": {
        "fusion.1": "jit(decode_fn)/embed/gather",
        "while.1": "jit(decode_fn)/layers/while",
        "fusion.2": L + "attn_qkv/dot_general",
        "fusion.3": L + "state_update/mul",
        "paged_decode_attention.1": L + "attn/paged_decode_attention",
        "fusion.4": L + "ssm_conv/add",
        "kernel.2": L + "mlp/moe_experts/gmm",
        "fusion.5": L + "mlp/moe_combine/reduce_sum"},
    "jit_prefill_fn": {
        "while.2": "jit(prefill_fn)/layers/while",
        "fusion.8": P + "kv_gather/gather",
        "fusion.9": P + "ssm_chunk/dot_general",
        "fusion.10": P + "state_reset/select_n",
        "kernel.3": P + "mlp/moe_experts/gmm"},
}
op = lambda name, s, e: [f"%{name} = f32[8]{{0}} fusion(%p), kind=kLoop", s, e]
PLANES = {
    "/device:TPU:0": {
        "XLA Modules": [["jit_decode_fn(1)", 0.00, 0.10],
                        ["jit_decode_fn(1)", 0.10, 0.20],
                        ["jit_prefill_fn(2)", 0.20, 0.35]],
        "XLA Ops": [
            op("fusion.1", 0.00, 0.01), op("while.1", 0.01, 0.10),
            op("fusion.2", 0.01, 0.02), op("fusion.3", 0.02, 0.045),
            op("paged_decode_attention.1", 0.045, 0.05),
            op("kernel.2", 0.05, 0.09), op("fusion.5", 0.09, 0.10),
            op("fusion.1", 0.10, 0.11), op("while.1", 0.11, 0.20),
            op("fusion.3", 0.11, 0.13), op("fusion.4", 0.13, 0.14),
            op("kernel.2", 0.14, 0.20),
            op("while.2", 0.20, 0.35), op("fusion.8", 0.20, 0.22),
            op("fusion.9", 0.22, 0.27), op("fusion.10", 0.27, 0.29),
            op("kernel.3", 0.29, 0.35)]},
    "/host:CPU": {"main": [["bench/window", 0.0, 0.4]]},
}
# read off PLANES by hand
UPDATE = 0.025 + 0.02
EXPERTS = 0.04 + 0.06 + 0.06
WINDOW = 0.4
ROW = {"iterations": 2, "prefill_launches": 0, "moe_experts_touched": 700,
       "moe_rows": 2 * 270, "moe_rows_max_expert": 9,
       "prefill_moe_experts_touched": 0, "prefill_moe_rows": 0}
TAIL = dict(ROW, prefill_launches=1, moe_experts_touched=760,
            prefill_moe_experts_touched=448, prefill_moe_rows=1500)
FENCES = [(-3.0, dict(ROW, moe_experts_touched=7)), (0.5, ROW),
          (1.5, dict(ROW, moe_experts_touched=800)),
          ] + [(70.0 + i, TAIL) for i in range(12)]


@pytest.fixture()
def traced(monkeypatch):
    """ctx with the hand-made trace (two decode launches and a prefill
    launch) and the fence rows, the registry holding the maps."""
    from test_scope_metrics import FakeCompiled
    monkeypatch.setattr(programs, "_programs", {})
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    monkeypatch.setattr(arch_mod, "_fences", fences(FENCES))
    for name, scopes in MAPS.items():
        programs.register(name, FakeCompiled(scopes))
    planes = {p: {l: [tuple(s) for s in spans] for l, spans in lines.items()}
              for p, lines in PLANES.items()}
    sizes = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "nemotron-3-nano-30b.json")))
    mix = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "serve-turns-steady.json")))
    return {"trace": trace_reduce.from_planes(planes),
            "cell": {"sizes": sizes, "mix": mix}, "fences_in_window": 2,
            "device": {"kind": "TPU v5 lite"}}


def test_region_seconds_by_hand(traced):
    secs = region_join.region_seconds(traced["trace"],
                                      nemotron_h_costs.LAYERED,
                                      nemotron_h_costs.MOE)
    assert secs["state_update"] == pytest.approx(UPDATE)
    assert secs["moe_experts"] == pytest.approx(EXPERTS)
    assert secs["ssm_chunk"] == pytest.approx(0.05)
    assert secs["state_reset"] == pytest.approx(0.02)
    assert secs["attn"] == pytest.approx(0.005)
    assert sum(secs.values()) == pytest.approx(0.35)


def test_every_reader_returns_a_number_on_a_trace_with_both_launches(traced):
    sizes = traced["cell"]["sizes"]
    expert = 2 * 2688 * 1920 * 2
    assert nemotron_h_costs.expert_bytes(sizes) == expert
    nbytes = (760 / 2 * 2 + 448) * expert + \
        2 * (2 * 270 / 2 * 2 + 1500) * 2688 * 2
    state = 2 * 7 * 96 * 64 * 64 * 128 * 4
    want = {
        "nemotron_h_moe_held_roofline": 100 * nbytes / 819e9 / EXPERTS,
        # the window's two rows: (700 + 800) of 2 x 2 launches x 7 x 64
        "nemotron_h_moe_held_touched_share": 100 * 1500 / (4 * 448),
        # two decode launches' state, read and written
        "nemotron_h_ssm_decode_roofline": 100 * 2 * state / 819e9 / UPDATE,
    }
    bench = harness.load_benchmark()
    listed = [m["name"] for m in bench["per_layer"]
              if m["name"] in tiny_nemotron_h.LISTS_THE_CELL
              and m["source"] == "device_trace"] + list(NEW)
    assert "prefill_chunk_ms" in listed and \
        "ssm_state_time_share.serve" in listed
    for name in listed:
        value = harness.read_metric(name, traced)
        assert value is not None and np.isfinite(value), name
        if name in want:
            assert value == pytest.approx(want[name]), name
    assert harness.read_metric("moe_time_share.serve", traced) == \
        pytest.approx(100 * (EXPERTS + 0.01) / WINDOW)
    assert harness.read_metric("ssm_state_time_share.serve", traced) == \
        pytest.approx(100 * (UPDATE + 0.01 + 0.05 + 0.02) / WINDOW)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_another_models_run(name, traced,
                                                    monkeypatch):
    """Another family's cell (Falcon-H1's has `state_update`, Sarvam's
    `moe_experts`), this family's on a program that counts nothing, a
    run without a trace: None, never 0 and never an error."""
    for other in ("falcon-h1-34b", "sarvam-105b"):
        sizes = json.load(open(os.path.join(
            REPO, "benchmark", "configs", other + ".json")))
        monkeypatch.setattr(region_join, "_last", (None, None, None))
        assert harness.read_metric(name, dict(
            traced, cell=dict(traced["cell"], sizes=sizes))) is None
    assert harness.read_metric(name, dict(traced, trace=None)) is None \
        or name in COUNTED
    monkeypatch.setattr(arch_mod, "_fences", fences([]))
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    if name != NEW[2]:              # the state's roofline reads no rows
        assert harness.read_metric(name, traced) is None
        # rows of a program before the counters (no such keys)
        monkeypatch.setattr(arch_mod, "_fences",
                            fences([(0.5, {"iterations": 4})]))
        monkeypatch.setattr(region_join, "_last", (None, None, None))
        assert harness.read_metric(name, traced) is None
    # a program without the regions (the parent commit's)
    from test_scope_metrics import FakeCompiled
    monkeypatch.setattr(programs, "_programs", {})
    programs.register("jit_decode_fn", FakeCompiled(
        {"fusion.3": "jit(decode_fn)/layers/attn/x"}))
    monkeypatch.setattr(arch_mod, "_fences", fences(FENCES))
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    assert harness.read_metric(name, traced) is None or name in COUNTED


def test_cost_functions_against_hand_counts():
    sizes = {"hidden_size": 6, "moe_intermediate_size": 5,
             "hybrid_override_pattern": "MEM*EME", "n_routed_experts": 3,
             "mamba_num_heads": 4, "mamba_head_dim": 2, "ssm_state_size": 7,
             "program": {}}
    assert nemotron_h_costs.expert_bytes(sizes) == 2 * 6 * 5 * 2
    wide = dict(sizes, program={"expert_width_stored": 8})
    assert nemotron_h_costs.expert_bytes(wide) == 2 * 6 * 8 * 2
    assert nemotron_h_costs.experts_held(sizes) == 9
    assert nemotron_h_costs.experts_traffic_bytes(sizes, 11, 9) == \
        11 * 120 + 2 * 9 * 6 * 2
    # 3 M layers x 5 slots x [4, 2, 7] float32, read and written
    assert nemotron_h_costs.state_bytes(sizes, 5) == 3 * 5 * 56 * 4
    assert nemotron_h_costs.decode_state_traffic_bytes(sizes, 5) == \
        2 * 3 * 5 * 56 * 4
    full = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "nemotron-3-nano-30b.json")))
    # the issue's counts at the stored width: 7 x 64 experts of 20.6 MB
    # (19.96 MB published: + 3.4%), and 2.1 MB of state a slot and layer
    assert nemotron_h_costs.expert_bytes(full) == 20_643_840
    assert nemotron_h_costs.expert_bytes(full) / (2 * 2688 * 1856 * 2) == \
        pytest.approx(1.0345, abs=1e-4)
    assert nemotron_h_costs.experts_held(full) == 448
    assert nemotron_h_costs.decode_state_traffic_bytes(full, 96) == \
        2 * 7 * 96 * 2_097_152


# ----------------------------------------------------------------------
# the files
# ----------------------------------------------------------------------
def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows
              if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
    return row


def test_configuration_keeps_every_published_value():
    """Every key of the catalog's row for the source at its published
    value, but for the depth, the pattern's prefix, the experts held
    and the vocabulary's slice, which `reduced` names; no width
    changed."""
    row = catalog_row()
    entry = tiny_nemotron_h.FULL_CONFIG
    with open(os.path.join(REPO, entry["file"])) as f:
        sizes = json.load(f)
    assert sizes["source"] == entry["source"] == row["source_url"]
    assert sizes["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    differs = {k for k, v in row["config"].items() if sizes[k] != v}
    assert differs == set(sizes["reduced"])
    assert (sizes["num_hidden_layers"], sizes["n_routed_experts"],
            sizes["vocab_size"], sizes["first_expert"]) == (16, 64, 65536, 0)
    assert sizes["hybrid_override_pattern"] == "MEMEM*EMEMEM*EME" == \
        row["config"]["hybrid_override_pattern"][:16]
    assert sizes["published"] == {k: row["config"][k]
                                  for k in sizes["reduced"]}
    # the widths the issue names, unchanged
    assert (sizes["hidden_size"], sizes["mamba_num_heads"],
            sizes["mamba_head_dim"], sizes["ssm_state_size"],
            sizes["n_groups"], sizes["conv_kernel"],
            sizes["num_attention_heads"], sizes["num_key_value_heads"],
            sizes["head_dim"], sizes["moe_intermediate_size"],
            sizes["moe_shared_expert_intermediate_size"],
            sizes["num_experts_per_tok"], sizes["routed_scaling_factor"]) == \
        (2688, 64, 64, 128, 8, 4, 32, 2, 128, 1856, 3712, 6, 2.5)
    # the floors: a whole period and four layers, 8 experts, an eighth
    # of the vocabulary
    assert sizes["n_routed_experts"] >= 8 and \
        8 * sizes["vocab_size"] >= sizes["published"]["vocab_size"]
    assert sizes["program"] == {"architecture": "nemotron_h",
                                "param_dtype": "bfloat16",
                                "expert_width_stored": 1920}
    assert set(sizes["assumed"]) >= {
        "router", "expert_bias", "no_rotation", "A_log", "dt_bias", "D",
        "experts", "weights", "initializer_range", "ssm_state_dtype"}
    assert all(isinstance(v, (int, float)) or len(v) > 40
               for k, v in sizes["assumed"].items()
               if k != "ssm_state_dtype")
    assert "TWO chips share each layer" in sizes["deployment"] and \
        "64 of the 128 routed experts" in sizes["deployment"] and \
        "10.87 GB" in sizes["deployment"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_the_programs_config_holds_the_published_values():
    """`NemotronHConfig()`'s defaults are the row's values, key for
    key, where it has the key."""
    import dataclasses
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig
    row = catalog_row()["config"]
    mine = dataclasses.asdict(NemotronHConfig())
    shared = set(mine) & set(row)
    assert len(shared) >= 26
    for key in shared:
        assert mine[key] == row[key], key


def test_the_benchmark_holds_the_cell_after_the_parents_entries():
    """`BENCHMARK.json` has the configuration, the cell and the three
    readers as `tiny_nemotron_h.py` gives them, each AFTER every entry
    the parent had in its list, the cell's name appended to the lists
    of the accepted metrics whose readers find something to read in it
    and to no other."""
    bench = harness.load_benchmark()
    names = lambda key: [e["name"] for e in bench[key]]
    assert bench["configs"][names("configs").index(
        "nemotron-3-nano-30b")] == tiny_nemotron_h.FULL_CONFIG
    cell = bench["workloads"][names("workloads").index(CELL)]
    assert {k: cell[k] for k in ("config", "traffic", "chips")} == {
        "config": "nemotron-3-nano-30b", "traffic": "serve-turns-steady",
        "chips": 1} and 20 < len(cell["why"]) <= 200
    assert names("configs").index("nemotron-3-nano-30b") > \
        names("configs").index("phi-4-mini-flash")
    assert names("workloads").index(CELL) > \
        names("workloads").index("phi-4-mini-flash.serve-think-steady")
    at = [names("per_layer").index(n) for n in NEW]
    assert at == list(range(at[0], at[0] + 3)) and \
        at[0] > names("per_layer").index("gmu_time_share.serve")
    by_name = {m["name"]: m
               for m in bench["end_to_end"] + bench["per_layer"]}
    for m in tiny_nemotron_h.NEW_PER_LAYER:
        assert by_name[m["name"]] == dict(m, workloads=[CELL])
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".py"))
    listed = {name for name, m in by_name.items()
              if CELL in m.get("workloads", [])}
    assert listed == set(tiny_nemotron_h.LISTS_THE_CELL) | set(NEW)
    for name in tiny_nemotron_h.LISTS_THE_CELL:
        cells = by_name[name]["workloads"]
        assert cells[-1] == CELL and cells.count(CELL) == 1
    for rel in (tiny_nemotron_h.FULL_CONFIG["file"],
                f"benchmark/traffic/{cell['traffic']}.json"):
        assert os.path.exists(os.path.join(REPO, rel)), rel
    # `layers` alone would count the state-space regions in (the closed
    # vocabulary of `scope_reduce`), and the other families' readers
    # take their rows from their own builders' sinks
    assert not listed & {
        "kv_pool_carry_time_share.serve", "moe_expert_roofline",
        "moe_held_roofline", "moe_held_touched_share",
        "moe_experts_touched_share", "ssm_decode_roofline",
        "state_resident_gb.serve", "mamba1_decode_roofline"}


def test_the_traffic_file_holds_the_issues_parameters():
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "serve-turns-steady.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "serve_open_arch" and mix["chips"] == 1
    assert mix["inference"] == {
        "max_slots": 96, "prefill_chunk": 512, "sync_every": 4,
        "max_new_tokens": 1024, "max_seq_len": 3072,
        "kv_cache": {"num_pages": 2305, "page_size": 128}}
    arrivals = mix["arrivals"]
    assert (arrivals["process"], arrivals["schedule_seed"],
            arrivals["seed_jitter_s"]) == ("jittered_grid", 45, 0.1)
    # 0.7 of the knee 10.0 that both seeds gave, down to 0.05/s; the
    # pre-roll the tool's rule gives at that rate (13.9 s to the
    # nearest 5)
    assert (arrivals["rate_per_s"], arrivals["preroll_s"]) == (7.0, 15)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.8, "min": 32, "max": 2048}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.7, "min": 16, "max": 1024}
    assert mix["max_total_tokens"] == 3072 and mix["drain_s"] == 15
    assert mix["tokens"] == {"dist": "uniform"}
    assert set(mix["check"]["limits"]) == {
        "live_logits_rel", "served_gap_max", "served_gap_mean",
        "ssm_state_rel", "router_picks_agree"}
    # between the sound runs' least (28 of 42 picks) and the fp8
    # control's largest (18 of 42)
    assert 18 / 42 < mix["check"]["limits"]["router_picks_agree"] < 28 / 42
    assert "my chip runs, PR 45" in mix["check"]["limits_set_from"]
    assert mix["control"] == {"reference_cast": "float8_e4m3fn"}
    assert "sweep_knee_kind.py" in mix["sized_by"]
    # every slot's worst case has its pages
    assert (mix["inference"]["kv_cache"]["num_pages"] - 1) * 128 == \
        96 * mix["max_total_tokens"]


def test_every_traced_tail_holds_a_prefill_launch():
    """`prefill_chunk_ms` lists the cell: the traced tail is twelve
    iterations of at least four decode launches (12 x 4 x 15 ms = 0.7
    s at the least a launch can take), and on every one of 200 seeds
    the schedule offers a request in EVERY span of 0.7 s from the
    window's open to the tail's end, so whenever the tail falls it
    holds a prefill launch (PR 31's rule)."""
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "serve-turns-steady.json")) as f:
        mix = json.load(f)
    widest = 0.0
    for seed in range(200):
        requests = traffic.serve_requests(mix, 65536, 51.0, 2**31 + seed,
                                          27.0)
        at = np.sort([r["arrival_s"] for r in requests])
        at = at[at >= 0]
        assert at[-1] > 51 + 15 + 10
        widest = max(widest, float(np.diff(at).max()))
    assert widest < 0.7
