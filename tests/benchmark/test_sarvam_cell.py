"""What ISSUE 39 adds to the benchmark, driven on the CPU at a tiny
size (`tiny_sarvam.py`): the Sarvam cell end to end through the kind
`serve_open_arch`; the fp8 reference, a dropped pick, a selection bias
left out, the latent's norm left out, the rotation on the wrong
values, m^2 left out of the scale, a pool held in fp8 and a row
written to the wrong slot each not correct; the new readers on the
program's own fence rows and on a trace made by hand with both
launches in it; the cost functions against hand counts; the files."""

import json
import os
import sys
import time

import numpy as np
import pytest

import tiny_copy
import tiny_sarvam
from benchmark import (harness, mla_costs, region_join, scope_reduce,
                       state_scopes, trace_reduce)
from benchmark.architectures import sarvam_mla as arch_mod
from deepspeed_tpu.monitor import programs
from test_trinity_cell import drop_a_pick, fences, leave_the_bias_out

SEED = 2**31 + 11
REPO = tiny_copy.REPO
CELL = tiny_sarvam.FULL_CELL
NEW = ("mla_decode_roofline", "mla_absorb_time_share.serve",
       "moe_held_touched_share", "moe_held_roofline")
COUNTED = NEW[2:3]                # a program counter: no device needed


@pytest.fixture()
def tiny(tmp_path, monkeypatch):
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    monkeypatch.setattr(state_scopes, "_last", (None, None))
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    return tiny_copy.point_harness_at(monkeypatch,
                                      tiny_sarvam.make(tmp_path))


def run(h, **kw):
    return h.run_cell(tiny_sarvam.CELL, SEED, 2.0, kw.pop("trace", 0),
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
    # a live slot's 4 picks of 16 over the 4 held x 2 layers: a launch
    # touches some of them, and with few slots live never all
    assert 5 < got["moe_held_touched_share"]["value"] < 100


# ----------------------------------------------------------------------
# faults, each read against the sound run's limits
# ----------------------------------------------------------------------
def rotate_the_wrong_values(monkeypatch):
    """The rotary key is rotated, the rotary part of the query is not:
    what a block that rotated the 64 of the wrong operand computes."""
    from deepspeed_tpu.models import sarvam_mla
    real = sarvam_mla.rope
    monkeypatch.setattr(
        sarvam_mla, "rope", lambda x, positions, freq:
        x if x.shape[2] > 1 else real(x, positions, freq))


def leave_the_latents_norm_out(monkeypatch):
    """The compressed vector goes into the row and into W_kvb as it
    is projected (the norm whose weight is `kv_lora_rank` wide)."""
    from deepspeed_tpu.models import sarvam_mla
    real = sarvam_mla.rms_norm
    monkeypatch.setattr(
        sarvam_mla, "rms_norm", lambda x, w, eps:
        x if w.shape[-1] == tiny_sarvam.TINY_SIZES["kv_lora_rank"]
        else real(x, w, eps))


def hold_the_pool_in_fp8(monkeypatch):
    import jax.numpy as jnp
    from deepspeed_tpu.inference.latent_kind import PagedLatentKind
    real = PagedLatentKind.__init__

    def init(self, *args):
        real(self, *args)
        self.dtype = jnp.dtype("float8_e4m3fn")
    monkeypatch.setattr(PagedLatentKind, "__init__", init)


def write_to_the_wrong_slot(monkeypatch):
    """Decode's rows are written through the table of the slot beside
    theirs."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.latent_kind import PagedLatentKind
    real = PagedLatentKind.mixer

    def mixer(self, tables, positions, valid, kv_limit):
        mix = real(self, tables, positions, valid, kv_limit)
        if tables.shape[0] == 1:
            return mix
        wrong = real(self, jnp.roll(tables, 1, axis=0), positions, valid,
                     kv_limit)
        return lambda li, q, row, pools: (
            mix(li, q, row, pools)[0], wrong(li, q, row, pools)[1], valid)
    monkeypatch.setattr(PagedLatentKind, "mixer", mixer)


# fault -> (the model keys a control lays over the config, the check
# that must be over its limit)
FAULTS = {
    None: (None, None),
    "fp8_reference": (None, "latent_rows_rel"),
    "dropped_pick": (None, "router_picks_agree"),
    "bias_left_out": (None, "router_picks_agree"),
    "latent_norm_left_out": (None, "latent_rows_rel"),
    "rotation_on_the_wrong_values": (None, "live_logits_rel"),
    "m2_left_out_of_the_scale": (
        {"rope_scaling": tuple(sorted(dict(
            tiny_sarvam.YARN, mscale_all_dim=0).items()))},
        "live_logits_rel"),
    "pool_held_in_fp8": (None, "latent_rows_rel"),
    "row_written_to_the_wrong_slot": (None, "latent_rows_rel"),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_live_slots_against_the_reference(tiny, monkeypatch, fault):
    """Slots in mid-flight, prompts of several launches behind them
    and decode steps through the latent pool: sound float32 agrees
    with the reference to rounding on the logits, on every latent row
    of the first and last layer and on every pick of every expert
    layer; each fault lies past a limit."""
    from benchmark.kinds import serve_open, serve_open_arch
    from deepspeed_tpu.inference import Request, ServingLoop
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_sarvam.CELL)
    model, broken = FAULTS[fault]
    if fault == "dropped_pick":
        drop_a_pick(monkeypatch)
    if fault == "bias_left_out":
        leave_the_bias_out(monkeypatch)
    if fault == "latent_norm_left_out":
        leave_the_latents_norm_out(monkeypatch)
    if fault == "rotation_on_the_wrong_values":
        rotate_the_wrong_values(monkeypatch)
    if fault == "row_written_to_the_wrong_slot":
        write_to_the_wrong_slot(monkeypatch)
    if fault == "pool_held_in_fp8":
        hold_the_pool_in_fp8(monkeypatch)
    engine, flat, ref = serve_open_arch.build_engine(
        cell, SEED, {"model": model} if model else None)
    arch = serve_open_arch.architecture(cell)
    assert arch is arch_mod
    # as the kind does, on an empty engine: nothing launched, no picks
    (empty,) = arch.live_state(engine, [0], 4)
    assert empty["picks"] is None and empty["rows"].shape == (2, 128, 40)
    loop = ServingLoop(engine)
    rng = np.random.default_rng(3)
    for i, (n, m) in enumerate([(70, 40), (30, 40), (85, 40), (50, 30)]):
        loop.submit(Request(rid=i, tokens=rng.integers(0, 512, n),
                            max_new_tokens=m))
    loop._t0, loop._last_fence_t = time.monotonic(), 0.0
    for _ in range(14):
        loop.step()
    live = serve_open.next_logits_of_live_slots(engine, loop, most=4)
    assert len(live) == 4 and all(len(seq) > 40 for seq, _ in live)
    cast = "float8_e4m3fn" if fault == "fp8_reference" else None
    (logits,) = serve_open_arch.compare_with_reference(
        ref, flat, cell["sizes"], cell["mix"]["check"], [], live, 128, 40,
        control_cast=cast)
    assert logits["name"] == "live_logits_rel"
    before = [id(x) for x in engine.cache_arrays()]
    states = arch.live_state(engine, sorted(loop.live), 4)
    assert all(s["picks"].shape == (2, 4) for s in states)
    # it reads what the launch gave out and touches nothing
    assert [id(x) for x in engine.cache_arrays()] == before
    assert len(arch.fence_rows({"cell": cell})) == 14
    checks = {c["name"]: c for c in arch.state_checks(
        flat, cell["sizes"], cell["mix"]["check"]["limits"],
        [(seq, got) for (seq, _), got in zip(live, states)], 128,
        control_cast=cast)}
    assert set(checks) == {"router_picks_agree", "latent_rows_rel",
                           "latent_rows_mean_rel", "latent_dtype_differs"}
    checks["live_logits_rel"] = logits
    if fault is None:
        assert all(c["ok"] for c in checks.values()), checks
        assert logits["value"] < 2e-5 and \
            checks["latent_rows_rel"]["value"] < 2e-5 and \
            checks["latent_rows_mean_rel"]["value"] < 2e-6 and \
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
    if fault == "pool_held_in_fp8":
        assert not checks["latent_dtype_differs"]["ok"], checks
    else:
        assert checks["latent_dtype_differs"]["ok"], checks


def test_an_older_program_refuses_the_architecture_cleanly(tiny,
                                                           monkeypatch):
    """The parent commit has no `models/sarvam_mla.py`: the builder
    says so with exit code 2 at once."""
    from benchmark.kinds import serve_open_arch
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_sarvam.CELL)
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.models.sarvam_mla", None)
    with pytest.raises(SystemExit) as refused:
        serve_open_arch.build_engine(cell, SEED)
    assert refused.value.code == 2


def test_weights_are_seeded_and_every_path_shows():
    import jax.numpy as jnp
    from benchmark import weights_sarvam_mla as weights
    sizes = tiny_sarvam.TINY_SIZES
    flat = weights.make_weights(sizes, SEED, jnp.bfloat16)
    again = weights.make_weights(sizes, SEED, jnp.bfloat16,
                                 only=("h.w_gate", "head", "h.expert_bias"))
    assert all(np.array_equal(flat[k], again[k]) for k in again)
    other = weights.make_weights(sizes, SEED + 1, jnp.bfloat16)
    assert not np.array_equal(flat["h.wq"], other["h.wq"])
    # the share: 4 experts held, the router 16 wide, the slice's rows
    assert flat["head"].shape == (64, 512) and \
        flat["embed"].shape == (512, 64) and \
        flat["h.w_gate"].shape == (2, 4, 64, 32) and \
        flat["h.w_down"].shape == (2, 4, 32, 64) and \
        flat["d.w_gate"].shape == (1, 64, 96) and \
        flat["h.router"].shape == (2, 64, 16) and \
        flat["h.expert_bias"].shape == (2, 16) and \
        flat["h.wq"].shape == (2, 64, 4 * 24) and \
        flat["h.w_kva"].shape == (2, 64, 40) and \
        flat["h.w_kvb"].shape == (2, 32, 4 * 32) and \
        flat["h.wo"].shape == (2, 64, 64) and \
        flat["h.kv_norm"].shape == (2, 32) and \
        flat["h.q_norm"].shape == (2, 24)
    assert str(flat["h.expert_bias"].dtype) == "float32" and \
        str(flat["h.wq"].dtype) == "bfloat16"
    norm = np.asarray(flat["h.kv_norm"], np.float32)
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


@pytest.mark.parametrize("seed", [SEED, 5])
def test_the_balanced_bias_loads_the_experts_evenly(tiny, seed):
    """Under the drawn bias the 16 experts' loads on fresh uniform
    tokens differ by tens of a hundred; under `balanced_bias`, on rows
    it was not balanced on, the most loaded expert is within a quarter
    of the mean (1,024 rows of balancing leave a tenth of noise), the
    bias sums to zero a layer, and the same seed gives the same
    bias."""
    import jax
    import jax.numpy as jnp
    from benchmark import weights_sarvam_mla as weights
    from benchmark.reference import sarvam_mla as reference
    sizes = tiny.load_cell(tiny.load_benchmark(), tiny_sarvam.CELL)["sizes"]
    flat = weights.make_weights(sizes, seed, jnp.float32)
    even = weights.balanced_bias(flat, sizes, seed, reference)
    assert even.shape == flat["h.expert_bias"].shape and \
        str(even.dtype) == "float32" and \
        np.abs(np.asarray(even).sum(1)).max() < 1e-5
    assert np.array_equal(even, weights.balanced_bias(flat, sizes, seed,
                                                      reference))
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, sizes["vocab_size"], (8, 250)), jnp.int32)

    def worst(bias):
        """The most loaded expert's load over the mean, by layer."""
        picks = jax.vmap(lambda row: reference._through(
            dict(flat, **{"h.expert_bias": bias}), row, sizes, None)[1])(ids)
        return [np.bincount(np.asarray(p).ravel(), minlength=16).max() /
                (np.asarray(p).size / 16) for p in picks]
    assert max(worst(even)) < 1.25 < min(worst(flat["h.expert_bias"]))


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
def test_regions_are_the_programs_vocabulary():
    from deepspeed_tpu.inference import latent_kind
    from deepspeed_tpu.utils import scopes
    assert mla_costs.LATENT_MOE == latent_kind.SCOPES_LATENT_MOE == \
        scopes.SCOPES_LATENT_MOE
    assert mla_costs.MOE == scopes.SCOPES_MOE
    assert mla_costs.ABSORB == (scopes.SCOPE_MLA_ABSORB,) == ("mla_absorb",)
    # what the paged programs and Trinity's name keeps its name
    assert set(scope_reduce.REGIONS) <= set(scopes.SCOPES_PAGED_MOE) < \
        set(mla_costs.LATENT_MOE)


L = "jit(decode_fn)/layers/while/body/closed_call/"
P = "jit(prefill_fn)/layers/while/body/closed_call/"
MAPS = {
    "jit_decode_fn": {
        "fusion.1": "jit(decode_fn)/embed/gather",
        "while.1": "jit(decode_fn)/layers/while",
        "fusion.2": L + "attn_qkv/dot_general",
        "fusion.3": L + "attn_qkv/mla_absorb/dot_general",
        "latent_decode_attention.1": L + "attn/latent_decode_attention",
        "fusion.4": L + "attn_out/mla_absorb/dot_general",
        "kernel.2": L + "mlp/moe_experts/gmm",
        "fusion.5": L + "mlp/moe_combine/reduce_sum"},
    "jit_prefill_fn": {
        "while.2": "jit(prefill_fn)/layers/while",
        "fusion.8": P + "kv_gather/gather",
        "fusion.9": P + "attn/dot_general",
        "fusion.10": P + "attn_qkv/mla_absorb/dot_general",
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
            op("fusion.2", 0.01, 0.02), op("fusion.3", 0.02, 0.025),
            op("latent_decode_attention.1", 0.025, 0.045),
            op("fusion.4", 0.045, 0.05), op("kernel.2", 0.05, 0.09),
            op("fusion.5", 0.09, 0.10),
            op("fusion.1", 0.10, 0.11), op("while.1", 0.11, 0.20),
            op("latent_decode_attention.1", 0.11, 0.14),
            op("kernel.2", 0.14, 0.20),
            op("while.2", 0.20, 0.35), op("fusion.8", 0.20, 0.22),
            op("fusion.9", 0.22, 0.27), op("fusion.10", 0.27, 0.29),
            op("kernel.3", 0.29, 0.35)]},
    "/host:CPU": {"main": [["bench/window", 0.0, 0.4]]},
}
# read off PLANES by hand
KERNEL = 0.02 + 0.03
ABSORB = 0.005 + 0.005 + 0.02
EXPERTS = 0.04 + 0.06 + 0.06
WINDOW = 0.4
# the fence rows the program would have logged: a window of two
# fences, then the traced tail's twelve (two decode launches and one
# prefill launch each)
ROW = {"iterations": 2, "prefill_launches": 0, "moe_experts_touched": 230,
       "moe_rows": 2 * 4 * 50 * 2, "moe_rows_max_expert": 9,
       "prefill_moe_experts_touched": 0, "prefill_moe_rows": 0,
       "kv_pages_attended": 1600, "kv_latent_bytes_resident": 3769139200}
TAIL = dict(ROW, prefill_launches=1, moe_experts_touched=250,
            kv_pages_attended=1700, prefill_moe_experts_touched=128,
            prefill_moe_rows=4 * 512 * 2)
FENCES = [(-3.0, dict(ROW, moe_experts_touched=7)), (0.5, ROW),
          (1.5, dict(ROW, moe_experts_touched=256)),
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
        REPO, "benchmark", "configs", "sarvam-105b.json")))
    mix = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "serve-assist-steady.json")))
    return {"trace": trace_reduce.from_planes(planes),
            "cell": {"sizes": sizes, "mix": mix}, "fences_in_window": 2,
            "device": {"kind": "TPU v5 lite"}}


def test_region_seconds_by_hand(traced):
    secs = region_join.region_seconds(traced["trace"], mla_costs.LATENT_MOE,
                                      mla_costs.ABSORB)
    assert secs["mla_absorb"] == pytest.approx(ABSORB)
    assert secs["moe_experts"] == pytest.approx(EXPERTS)
    assert secs["attn"] == pytest.approx(KERNEL + 0.05)
    # the projections round the absorption stay under `attn_qkv`
    assert secs["attn_qkv"] == pytest.approx(0.01)
    assert sum(secs.values()) == pytest.approx(0.35)


def test_every_reader_returns_a_number_on_a_trace_with_both_launches(traced):
    sizes = traced["cell"]["sizes"]
    expert = 3 * 4096 * 2048 * 2
    assert mla_costs.expert_bytes(sizes) == expert
    # the tail's rows: 1,700 pages of 128 tokens a launch, 2 kernel events
    tokens = 2 * 1700 * 128
    nbytes = (250 / 2 * 2 + 128) * expert + \
        2 * (2 * 4 * 50 * 2 / 2 * 2 + 4 * 512 * 2) * 4096 * 2
    want = {
        # memory is the bound: 576 values of 2 bytes a token
        "mla_decode_roofline": 100 * tokens * 576 * 2 / 819e9 / KERNEL,
        "mla_absorb_time_share.serve": 100 * ABSORB / WINDOW,
        # the window's two rows: (230 + 256) of 2 x 2 launches x 4 x 32
        "moe_held_touched_share": 100 * 486 / (4 * 128),
        "moe_held_roofline": 100 * nbytes / 819e9 / EXPERTS,
    }
    assert 64 * (576 + 512) * 2 / (576 * 2) < 197e12 / 819e9
    bench = harness.load_benchmark()
    listed = [m["name"] for m in bench["per_layer"]
              if m["name"] in tiny_sarvam.LISTS_THE_CELL
              and m["source"] == "device_trace"] + list(NEW)
    for name in listed:
        value = harness.read_metric(name, traced)
        assert value is not None and np.isfinite(value), name
        if name in want:
            assert value == pytest.approx(want[name]), name
    assert harness.read_metric("attention_time_share.serve", traced) == \
        pytest.approx(100 * (KERNEL + 0.05) / WINDOW)
    assert harness.read_metric("moe_time_share.serve", traced) == \
        pytest.approx(100 * (EXPERTS + 0.01) / WINDOW)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_another_models_run(name, traced,
                                                    monkeypatch):
    """The other models' programs (and the parent commit's) have no
    `mla_absorb` region, no such kernel, and log no such rows: None,
    never 0 and never an error."""
    from test_scope_metrics import FakeCompiled
    others = {"jit_decode_fn": {
        "fusion.3": "jit(decode_fn)/layers/attn/x",
        "kernel.2": "jit(decode_fn)/layers/mlp/moe_experts/gmm"}}
    monkeypatch.setattr(programs, "_programs", {})
    for program, scopes in others.items():
        programs.register(program, FakeCompiled(scopes))
    other = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "trinity-mini.json")))
    planes = {p: {l: [tuple(s) for s in spans if
                      "latent_decode" not in s[0]]
                  for l, spans in lines.items()}
              for p, lines in PLANES.items()}
    elsewhere = dict(traced, trace=trace_reduce.from_planes(planes),
                     cell=dict(traced["cell"], sizes=other))
    assert harness.read_metric(name, elsewhere) is None
    # this architecture's cell on a program that counts nothing (no
    # rows), and a run without a trace
    monkeypatch.setattr(arch_mod, "_fences", fences([]))
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    assert harness.read_metric(name, dict(traced, trace=None)) is None
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    assert harness.read_metric(name, traced) is None
    # rows of a program before the counters (no such keys)
    monkeypatch.setattr(arch_mod, "_fences",
                        fences([(0.5, {"iterations": 4})]))
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    assert harness.read_metric(name, traced) is None


def test_cost_functions_against_hand_counts():
    sizes = {"hidden_size": 6, "moe_intermediate_size": 5,
             "num_hidden_layers": 7, "first_k_dense_replace": 2,
             "num_experts": 3, "kv_lora_rank": 8, "qk_rope_head_dim": 2,
             "num_attention_heads": 4}
    assert mla_costs.expert_bytes(sizes) == 3 * 6 * 5 * 2
    assert mla_costs.expert_layers(sizes) == 5
    assert mla_costs.experts_held(sizes) == 15
    assert mla_costs.latent_row_values(sizes) == 10
    # 11 tokens: a row of 10 values each; 4 heads x (10 + 8) products
    assert mla_costs.decode_attention_cost(sizes, 11) == (
        2 * 4 * 18 * 11, 11 * 10 * 2)
    assert mla_costs.experts_traffic_bytes(sizes, 11, 9) == \
        11 * 180 + 2 * 9 * 6 * 2
    full = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "sarvam-105b.json")))
    # the issue's counts: 1,152 B and 139 kFLOP a cached token and layer,
    # 4 x 32 experts of 50.3 MB: 6.44 GB a step
    flops, nbytes = mla_costs.decode_attention_cost(full, 1)
    assert (flops, nbytes) == (139264, 1152)
    assert mla_costs.experts_held(full) * mla_costs.expert_bytes(full) == \
        6_442_450_944


# ----------------------------------------------------------------------
# the files
# ----------------------------------------------------------------------
def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "sarvam-105b"]
    return row


def test_configuration_keeps_every_published_value():
    """Every key of the catalog's row for the source at its published
    value, but for the depth, the experts held and the vocabulary's
    slice, which `reduced` names; no width changed."""
    row = catalog_row()
    entry = tiny_sarvam.FULL_CONFIG
    with open(os.path.join(REPO, entry["file"])) as f:
        sizes = json.load(f)
    assert sizes["source"] == entry["source"] == row["source_url"]
    assert sizes["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    differs = {k for k, v in row["config"].items() if sizes[k] != v}
    assert differs == set(sizes["reduced"])
    assert (sizes["num_hidden_layers"], sizes["num_experts"],
            sizes["vocab_size"], sizes["first_expert"]) == (5, 32, 65536, 0)
    assert sizes["published"] == {k: row["config"][k]
                                  for k in sizes["reduced"]}
    # the floors: four layers behind the dense one, 8 experts, an
    # eighth of the vocabulary; the router and the picks whole
    assert sizes["num_hidden_layers"] - sizes["first_k_dense_replace"] >= 4
    assert sizes["num_experts"] >= 8 and \
        8 * sizes["vocab_size"] >= sizes["published"]["vocab_size"]
    assert sizes["num_experts_per_tok"] == 8
    assert sizes["program"] == {"architecture": "sarvam_mla",
                                "param_dtype": "bfloat16"}
    assert set(sizes["assumed"]) >= {
        "pre_norm", "qk_norm", "rope", "router", "expert_bias", "experts",
        "embedding", "weights", "initializer_range", "dtype"}
    assert "FOUR chips share each layer" in sizes["deployment"] and \
        "32 of the 128 routed experts" in sizes["deployment"] and \
        "9.07 GB" in sizes["deployment"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_the_programs_config_holds_the_published_values():
    """`SarvamMLAConfig()`'s defaults are the row's values, key for
    key, where it has the key."""
    import dataclasses
    from deepspeed_tpu.models.sarvam_mla import SarvamMLAConfig
    row = catalog_row()["config"]
    mine = dataclasses.asdict(SarvamMLAConfig())
    shared = set(mine) & set(row)
    assert len(shared) >= 22
    for key in shared:
        want = tuple(sorted(row[key].items())) \
            if isinstance(row[key], dict) else row[key]
        assert mine[key] == want, key
    assert SarvamMLAConfig().latent_row == row["head_dim"] == 576


def test_the_benchmark_holds_the_cell_after_the_parents_entries():
    """`BENCHMARK.json` has the configuration, the cell and the four
    readers as `tiny_sarvam.py` gives them, each AFTER every entry the
    parent had in its list (by name: a later PR appends after these),
    the cell's name appended to the lists of the accepted metrics whose
    readers find something to read in it and to no other (none of
    another family's readers is among them)."""
    bench = harness.load_benchmark()
    names = lambda key: [e["name"] for e in bench[key]]
    assert bench["configs"][names("configs").index("sarvam-105b")] == \
        tiny_sarvam.FULL_CONFIG
    assert bench["workloads"][names("workloads").index(CELL)] == \
        tiny_sarvam.FULL_WORKLOAD
    assert names("configs").index("sarvam-105b") > \
        names("configs").index("trinity-mini")
    assert names("workloads").index(CELL) > \
        names("workloads").index("trinity-mini.serve-reason-steady")
    at = [names("per_layer").index(n) for n in NEW]
    assert at == list(range(at[0], at[0] + 4)) and \
        at[0] > names("per_layer").index("dispatch_exposed_ms.serve")
    by_name = {m["name"]: m
               for m in bench["end_to_end"] + bench["per_layer"]}
    for m in tiny_sarvam.NEW_PER_LAYER:
        assert by_name[m["name"]] == dict(m, workloads=[CELL])
    listed = {name for name, m in by_name.items()
              if CELL in m.get("workloads", [])}
    assert listed == set(tiny_sarvam.LISTS_THE_CELL) | set(NEW)
    for name in tiny_sarvam.LISTS_THE_CELL:
        cells = by_name[name]["workloads"]
        assert cells.index(CELL) > cells.index(
            "trinity-mini.serve-reason-steady") and cells.count(CELL) == 1
    cell = tiny_sarvam.FULL_WORKLOAD
    assert cell["name"] == CELL and cell["chips"] == 1 and \
        20 < len(cell["why"]) <= 200 and \
        len(tiny_sarvam.FULL_CONFIG["why"]) <= 200
    for rel in (tiny_sarvam.FULL_CONFIG["file"],
                f"benchmark/traffic/{cell['traffic']}.json"):
        assert os.path.exists(os.path.join(REPO, rel)), rel
    mine = tiny_sarvam.NEW_PER_LAYER
    assert [m["name"] for m in mine] == list(NEW)
    for m in mine:
        assert m["unit"] == "%" and os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".py"))
    layers = {m["name"]: (m["layer"], m["moves"], m["source"])
              for m in mine}
    assert layers == {
        NEW[0]: ("kernels (latent decode)", "itl_mean_ms", "device_trace"),
        NEW[1]: ("model", "itl_mean_ms", "device_trace"),
        NEW[2]: ("model", "itl_mean_ms", "program_counter"),
        NEW[3]: ("kernels (moe)", "itl_mean_ms", "device_trace")}
    joins = set(tiny_sarvam.LISTS_THE_CELL)
    assert all("workloads" in by_name[name] for name in joins)
    assert joins >= {"itl_mean_ms", "serve_tokens_per_s",
                     "decode_iter_ms", "program_temp_gb.serve",
                     "device_idle_share.serve", "attention_time_share.serve",
                     "kv_gather_time_share.serve", "peak_hbm_gb.serve",
                     "weight_matmul_time_share.serve",
                     "unscoped_time_share.serve", "slots_occupied_mean",
                     "moe_time_share.serve", "host_exposed_ms.serve",
                     # the pool rides two layer scans' carry: a pool-sized
                     # copy would show under `layers` alone, where the
                     # slicing of a layer's weights out of the stack shows
                     "kv_pool_carry_time_share.serve",
                     # the traced tail starts at the first token of the
                     # window's last request (9 chunks from 50.86 s); the
                     # schedule's next (16 chunks) arrives 0.36-1.16 s
                     # after the window closes, behind it: every tail
                     # holds its chunks
                     "prefill_chunk_ms"}
    # Trinity's two readers of the experts take their rows from its own
    # builder's sink and count ALL experts; the other models' regions
    # do not exist in this model's programs
    assert not joins & {"moe_expert_roofline", "moe_experts_touched_share",
                        "kv_window_resident_share.serve",
                        "state_resident_gb.serve",
                        "retention_state_time_share.serve",
                        "retention_decode_roofline",
                        "retention_prefill_roofline",
                        "ssm_state_time_share.serve", "ssm_decode_roofline"}


def test_the_traffic_file_holds_the_issues_parameters():
    with open(os.path.join(REPO, "benchmark", "traffic",
                           tiny_sarvam.FULL_WORKLOAD["traffic"] +
                           ".json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "serve_open_arch" and mix["chips"] == 1
    inference = dict(mix["inference"])
    pool = inference.pop("kv_cache")
    assert inference == {"max_slots": 96, "prefill_chunk": 512,
                         "sync_every": 4, "max_new_tokens": 2560,
                         "max_seq_len": 10752}
    assert pool["page_size"] == 128 and 4000 < pool["num_pages"] < 5200
    arrivals = mix["arrivals"]
    assert (arrivals["process"], arrivals["schedule_seed"],
            arrivals["seed_jitter_s"]) == ("jittered_grid", 39, 0.4)
    # 0.7 of the knee 1.5 the sweep found, down to 0.05; the pre-roll
    # the tool's rule gives at that rate
    assert (arrivals["rate_per_s"], arrivals["preroll_s"]) == (1.05, 55)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 3072,
                                    "sigma": 0.6, "min": 512, "max": 8192}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1280,
                                    "sigma": 0.5, "min": 512, "max": 2560}
    assert mix["max_total_tokens"] == 10752 and mix["drain_s"] == 15
    assert mix["tokens"] == {"dist": "uniform"}
    assert (mix["check"]["requests"], mix["check"]["live_slots"]) == (3, 4)
    assert set(mix["check"]["limits"]) == {
        "live_logits_rel", "served_gap_max", "served_gap_mean",
        "router_picks_agree", "latent_rows_rel", "latent_rows_mean_rel"}
    assert 0.5 < mix["check"]["limits"]["router_picks_agree"] < 1
    assert "my chip runs, PR 39" in mix["check"]["limits_set_from"]
    assert mix["control"] == {"reference_cast": "float8_e4m3fn"}
    assert "sweep_knee_kind.py" in mix["sized_by"]
