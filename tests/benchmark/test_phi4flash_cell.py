"""What ISSUE 41 adds to the benchmark, driven on the CPU at a tiny
size (`tiny_phi4flash.py`): the Phi-4-mini-flash cell end to end
through the kind `serve_open_arch`; the fp8 reference, `mem` taken after
the gate, a cross layer reading a ring in place of the shared pool,
`lam` left out, the sub-norm left out, a window of 13 for 12, a state
kept in bfloat16 and prefill stopped a period short each NOT correct;
the new readers on the program's own fence rows and on a trace made by
hand with both launches in it; the cost functions against hand counts;
the files, by membership."""

import json
import os
import sys
import time

import numpy as np
import pytest

import tiny_copy
import tiny_phi4flash
from benchmark import (harness, phi4flash_costs, phi4flash_regions,
                       region_join, scope_reduce, state_scopes, trace_reduce)
from benchmark.architectures import phi4flash as arch_mod
from deepspeed_tpu.monitor import programs
from test_trinity_cell import fences

SEED = 2**31 + 41
REPO = tiny_copy.REPO
CELL = tiny_phi4flash.FULL_CELL
NEW = ("shared_kv_time_share.serve", "shared_kv_decode_roofline",
       "mamba1_decode_roofline", "gmu_time_share.serve")
BROUGHT_NOT_LISTED = "mamba1_prefill_roofline"


@pytest.fixture()
def tiny(tmp_path, monkeypatch):
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    monkeypatch.setattr(state_scopes, "_last", (None, None))
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    return tiny_copy.point_harness_at(monkeypatch,
                                      tiny_phi4flash.make(tmp_path))


def run(h, **kw):
    return h.run_cell(tiny_phi4flash.CELL, SEED, 2.0, kw.pop("trace", 0),
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
    (all four new ones among them) have nothing to read and are left
    out; the host's and the program's counters are there."""
    result = run(tiny, trace=1)
    assert result["correct"]
    got = result["metrics"]
    assert set(got) >= {"ttft_observed_mean_ms", "itl_p95_ms",
                        "slots_occupied_mean", "compiles_in_window.serve",
                        "peak_hbm_gb.serve", "queue_wait_mean_ms",
                        "program_temp_gb.serve"}
    assert not set(NEW) & set(got)
    rows = arch_mod.fence_rows({"cell": tiny.load_cell(
        tiny.load_benchmark(), tiny_phi4flash.CELL)})
    assert rows and all(
        {"kv_pages_shared_in_use", "kv_pages_shared_attended",
         "kv_pages_window_in_use", "prefill_layers_run",
         "state_slots_in_use"} <= set(row) for row in rows)
    # a fence's prefill launches ran 5 of the tiny model's 8 layers each
    assert all(row["prefill_layers_run"] == 5 * row["prefill_launches"]
               for row in rows)
    live = [row for row in rows if row["active_slots"]]
    assert any(row["kv_pages_shared_attended"] > 0 and
               row["kv_pages_shared_attended"] % 2 == 0 for row in live)


# ----------------------------------------------------------------------
# faults, each read against the sound run's limits
# ----------------------------------------------------------------------
def take_mem_after_the_gate(monkeypatch):
    from deepspeed_tpu.models import phi4flash
    monkeypatch.setattr(phi4flash, "memory_of", lambda y, gated: gated)


def leave_lam_out(monkeypatch):
    """The second softmax is never subtracted."""
    from deepspeed_tpu.models import phi4flash
    monkeypatch.setattr(phi4flash, "lam_of", lambda lp: 0.0)


def leave_the_subnorm_out(monkeypatch):
    from deepspeed_tpu.models import phi4flash
    monkeypatch.setattr(phi4flash, "sub_norm", lambda cfg, o, weight: o)


def read_a_ring_in_place_of_the_shared_pool(monkeypatch):
    """A cross layer walks the first window layer's ring."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference import hybrid_kind
    real = hybrid_kind.StateWindowSharedKind.paged

    def paged(self, tables, ring_tables, positions, valid, kv_limit):
        roles = real(self, tables, ring_tables, positions, valid, kv_limit)
        pos = positions[:, 0]
        live_len = jnp.where(valid.any(axis=1), kv_limit + 1, 0)

        def shared(li, q, cache):
            return hybrid_kind.diff_decode_attention(
                q[:, 0], cache[2], cache[3], 0, ring_tables, pos, live_len,
                self.mc.n_head, first=jnp.maximum(pos - self.window + 1, 0),
                ring=ring_tables.shape[1])[:, None]
        return dict(roles, shared=shared)
    monkeypatch.setattr(hybrid_kind.StateWindowSharedKind, "paged", paged)


def stop_prefill_a_period_short(monkeypatch):
    """The prefill program ends with the self-decoder: the middle
    period's state and the shared pool's rows are never written."""
    from deepspeed_tpu.models import phi4flash
    real = phi4flash.stacks
    monkeypatch.setattr(
        phi4flash, "stacks", lambda cfg, params, caching=False:
        real(cfg, params, caching)[:1 if caching else None])


# fault -> (model override, how it is made, the check it fails)
FAULTS = {
    None: (None, None, None),
    "fp8_reference": (None, None, "live_logits_rel"),
    "mem_after_the_gate": (None, take_mem_after_the_gate, "live_logits_rel"),
    "cross_layer_reads_a_ring": (
        None, read_a_ring_in_place_of_the_shared_pool, "live_logits_rel"),
    "lam_left_out": (None, leave_lam_out, "live_logits_rel"),
    "subnorm_left_out": (None, leave_the_subnorm_out, "live_logits_rel"),
    "window_of_13": ({"sliding_window": 13}, None, "live_logits_rel"),
    "state_in_bfloat16": ({"state_dtype": "bfloat16"}, None,
                          "scan_state_rel"),
    "prefill_a_period_short": (None, stop_prefill_a_period_short,
                               "shared_rows_rel"),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_live_slots_against_the_reference(tiny, monkeypatch, fault):
    """Slots in mid-flight, prompts of several launches behind them
    (each longer than the window and than two pages) and decode steps
    over state, rings and the shared pool: sound float32 agrees with
    the reference to rounding on the logits, on every element of layer
    0's state and on every row of the shared pool; each fault lies
    past a limit."""
    from benchmark.kinds import serve_open, serve_open_arch
    from deepspeed_tpu.inference import Request, ServingLoop
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_phi4flash.CELL)
    model, make, broken = FAULTS[fault]
    if make is not None:
        make(monkeypatch)
    engine, flat, ref = serve_open_arch.build_engine(
        cell, SEED, {"model": model} if model else None)
    arch = serve_open_arch.architecture(cell)
    assert arch is arch_mod
    (empty,) = arch.live_state(engine, [0], 4)   # as the kind does
    assert empty["S"].shape == (16, 128) and empty["k"].shape == (128, 32)
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
    before = [id(x) for x in engine.cache_arrays()]
    states = arch.live_state(engine, sorted(loop.live), 4)
    assert [id(x) for x in engine.cache_arrays()] == before
    assert len(arch.fence_rows({"cell": cell})) == 14
    checks = {c["name"]: c for c in arch.state_checks(
        flat, cell["sizes"], cell["mix"]["check"]["limits"],
        [(seq, got) for (seq, _), got in zip(live, states)], 128,
        control_cast=cast)}
    assert set(checks) == {"scan_state_rel", "shared_rows_rel",
                           "state_dtype_differs"}
    checks["live_logits_rel"] = logits
    if fault is None:
        assert all(c["ok"] for c in checks.values()), checks
        assert logits["value"] < 2e-5 and \
            checks["scan_state_rel"]["value"] < 2e-5 and \
            checks["shared_rows_rel"]["value"] < 2e-6, checks
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
    assert checks[broken]["value"] > 10 * checks[broken]["limit"], checks
    assert checks["state_dtype_differs"]["ok"] == \
        (fault != "state_in_bfloat16"), checks


def test_an_older_program_refuses_the_architecture_cleanly(tiny,
                                                           monkeypatch):
    """The parent commit has no `models/phi4flash.py`: the builder says
    so with exit code 2 at once."""
    from benchmark.kinds import serve_open_arch
    cell = tiny.load_cell(tiny.load_benchmark(), tiny_phi4flash.CELL)
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.models.phi4flash", None)
    with pytest.raises(SystemExit) as refused:
        serve_open_arch.build_engine(cell, SEED)
    assert refused.value.code == 2


def test_weights_are_seeded_and_lie_as_the_program_holds_them():
    import jax
    import jax.numpy as jnp
    from benchmark import weights_phi4flash as w
    from deepspeed_tpu.models import phi4flash
    sizes = tiny_phi4flash.TINY_SIZES
    a, b, c = (w.make_weights(sizes, s, jnp.float32) for s in (1, 1, 2))
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["embed"] == c["embed"]).all()
    alone = w.make_weights(sizes, 1, jnp.float32, only=("s.a.w_in",))
    assert (alone["s.a.w_in"] == a["s.a.w_in"]).all()
    cfg, _, tree, _ = arch_mod.build(sizes, 1)
    want = jax.eval_shape(lambda k: phi4flash.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree) == \
        jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)
    np.testing.assert_allclose(a["s.a.A_log_t"][:, :, 0],
                               np.log(np.arange(1., 17.))[None].repeat(2, 0),
                               rtol=1e-6)
    dt = jax.nn.softplus(a["m.a.dt_bias"])
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    assert a["c.b.lq1"].dtype == jnp.float32 and \
        0.05 < float(a["c.b.lq1"].std()) < 0.2
    assert abs(float(a["s.b.norm_w"].mean()) - 1) < 0.05


# ----------------------------------------------------------------------
# the readers on a trace made by hand
# ----------------------------------------------------------------------
def test_regions_are_the_programs_vocabulary():
    from deepspeed_tpu.inference import hybrid_kind
    from deepspeed_tpu.utils import scopes
    assert set(phi4flash_regions.HYBRID) == set(hybrid_kind.SCOPES_HYBRID) \
        == set(scopes.SCOPES_HYBRID)
    assert phi4flash_regions.NEW == (scopes.SCOPE_SHARED_KV,
                                     scopes.SCOPE_GMU)
    assert set(region_join.PAGED_STATE) < set(phi4flash_regions.HYBRID)
    assert hybrid_kind.SHARED_KERNEL == "shared_kv_decode_attention"


L = "jit(decode_fn)/layers/while/body/closed_call/"
P = "jit(prefill_fn)/layers/while/body/closed_call/"
MAPS = {
    "jit_decode_fn": {
        "fusion.1": "jit(decode_fn)/embed/gather",
        "while.1": "jit(decode_fn)/layers/while",
        "fusion.2": L + "attn_qkv/dot_general",
        "fusion.3": L + "state_update/mul",
        "diff_decode_attention.1": L + "attn/diff_decode_attention",
        "shared_kv_decode_attention.1":
            L + "attn/shared_kv/shared_kv_decode_attention",
        "fusion.4": L + "attn/shared_kv/transpose",
        "fusion.5": L + "attn_qkv/gmu/dot_general",
        "fusion.6": L + "attn_out/gmu/dot_general",
        "fusion.7": L + "mlp/dot_general"},
    "jit_prefill_fn": {
        "while.2": "jit(prefill_fn)/layers/while",
        "mamba1_selective_scan.1": P + "ssm_chunk/mamba1_selective_scan",
        "fusion.8": P + "kv_gather/gather",
        "fusion.9": P + "attn/dot_general",
        "fusion.10": P + "mlp/dot_general"},
}
op = lambda name, s, e: [f"%{name} = f32[8]{{0}} fusion(%p), kind=kLoop", s, e]
PLANES = {
    "/device:TPU:0": {
        "XLA Modules": [["jit_decode_fn(1)", 0.00, 0.10],
                        ["jit_decode_fn(1)", 0.10, 0.20],
                        ["jit_prefill_fn(2)", 0.20, 0.35]],
        "XLA Ops": [
            op("fusion.1", 0.00, 0.01), op("while.1", 0.01, 0.10),
            op("fusion.2", 0.01, 0.02), op("fusion.3", 0.02, 0.03),
            op("diff_decode_attention.1", 0.03, 0.035),
            op("shared_kv_decode_attention.1", 0.035, 0.06),
            op("fusion.4", 0.06, 0.065), op("fusion.5", 0.065, 0.07),
            op("fusion.6", 0.07, 0.08), op("fusion.7", 0.08, 0.10),
            op("fusion.1", 0.10, 0.11), op("while.1", 0.11, 0.20),
            op("fusion.3", 0.11, 0.12),
            op("shared_kv_decode_attention.1", 0.12, 0.15),
            op("fusion.7", 0.15, 0.20),
            op("while.2", 0.20, 0.35),
            op("mamba1_selective_scan.1", 0.20, 0.24),
            op("fusion.8", 0.24, 0.25), op("fusion.9", 0.25, 0.27),
            op("fusion.10", 0.27, 0.35)]},
    "/host:CPU": {"main": [["bench/window", 0.0, 0.4]]},
}
# read off PLANES by hand
KERNEL = 0.025 + 0.03
SHARED = KERNEL + 0.005
GMU = 0.005 + 0.01
STEP = 0.01 + 0.01
SCAN = 0.04
WINDOW = 0.4
ROW = {"iterations": 2, "prefill_launches": 0,
       "kv_pages_shared_attended": 8 * 1200, "kv_pages_shared_in_use": 1300,
       "kv_pages_window_in_use": 100, "prefill_layers_run": 0}
TAIL = dict(ROW, prefill_launches=1, kv_pages_shared_attended=8 * 1250,
            prefill_layers_run=17)
FENCES = [(-3.0, ROW), (0.5, ROW), (1.5, ROW)] + \
    [(70.0 + i, TAIL) for i in range(12)]


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
        REPO, "benchmark", "configs", "phi-4-mini-flash.json")))
    mix = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "serve-think-steady.json")))
    return {"trace": trace_reduce.from_planes(planes),
            "cell": {"sizes": sizes, "mix": mix}, "fences_in_window": 2,
            "device": {"kind": "TPU v5 lite"}}


def test_region_seconds_by_hand(traced):
    secs = region_join.region_seconds(
        traced["trace"], phi4flash_regions.HYBRID, phi4flash_regions.NEW)
    assert secs["shared_kv"] == pytest.approx(SHARED)
    assert secs["gmu"] == pytest.approx(GMU)
    assert secs["attn"] == pytest.approx(0.005 + 0.02)   # a ring's, prefill's
    assert secs["state_update"] == pytest.approx(STEP)
    assert secs["ssm_chunk"] == pytest.approx(SCAN)
    assert sum(secs.values()) == pytest.approx(0.35)


def test_every_reader_returns_a_number_on_a_trace_with_both_launches(traced):
    sizes = traced["cell"]["sizes"]
    slots = traced["cell"]["mix"]["inference"]["max_slots"]
    # the tail's rows: 1,250 live pages a launch, read once by each of
    # the trace's 2 kernel events; K and V of 1,280 values, 2 bytes
    reads = 2 * 1250
    want = {
        "shared_kv_time_share.serve": 100 * SHARED / WINDOW,
        "gmu_time_share.serve": 100 * GMU / WINDOW,
        "shared_kv_decode_roofline":
            100 * reads * 128 * 1280 * 2 * 2 / 819e9 / KERNEL,
        # 9 layers x 64 slots x [5120, 16] float32, read and written,
        # in each of two launches
        "mamba1_decode_roofline":
            100 * 2 * (2 * 9 * slots * 5120 * 16 * 4) / 819e9 / STEP,
        # memory bounds it: 9 layers of 512 x 5120 x 8 bytes and more
        BROUGHT_NOT_LISTED: 100 * 9 * (
            512 * 5120 * 8 + 512 * 32 * 2 + 2 * 5120 * 16 * 4) / 819e9 / SCAN,
    }
    for name, value in want.items():
        got = harness.read_metric(name, traced)
        assert got == pytest.approx(value), name
        assert 0 < got < 100, name
    bench = harness.load_benchmark()
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])
              and m["source"] == "device_trace"]
    assert set(NEW) <= set(listed)
    for name in listed:
        value = harness.read_metric(name, traced)
        assert value is not None and np.isfinite(value), name
    # the readers that exist serve this cell: all attention, ring's and
    # shared pool's and a chunk's; the scan and the step; the weights'
    # regions with the memory unit's two products inside them
    assert harness.read_metric("attention_time_share.serve", traced) == \
        pytest.approx(100 * (SHARED + 0.025) / WINDOW)
    assert harness.read_metric("ssm_state_time_share.serve", traced) == \
        pytest.approx(100 * (STEP + SCAN) / WINDOW)
    assert harness.read_metric("weight_matmul_time_share.serve", traced) == \
        pytest.approx(100 * (0.01 + GMU + 0.07 + 0.08) / WINDOW)


@pytest.mark.parametrize("name", NEW + (BROUGHT_NOT_LISTED,))
def test_readers_find_nothing_in_another_models_run(name, traced,
                                                    monkeypatch):
    """The other models' programs (and the parent commit's) have no
    `shared_kv` or `gmu` region, no such kernel, and log no such rows:
    None, never 0 and never an error."""
    from test_scope_metrics import FakeCompiled
    others = {"jit_decode_fn": {
        "fusion.3": "jit(decode_fn)/layers/state_update/x",
        "fusion.7": "jit(decode_fn)/layers/mlp/dot_general"},
        "jit_prefill_fn": {
        "fusion.9": "jit(prefill_fn)/layers/ssm_chunk/dot_general"}}
    monkeypatch.setattr(programs, "_programs", {})
    monkeypatch.setattr(region_join, "_last", (None, None, None))
    for program, scopes in others.items():
        programs.register(program, FakeCompiled(scopes))
    planes = dict(PLANES)
    planes["/device:TPU:0"] = dict(
        PLANES["/device:TPU:0"],
        **{"XLA Ops": [o for o in PLANES["/device:TPU:0"]["XLA Ops"]
                       if "shared_kv_decode" not in o[0]]})
    ctx = dict(traced, trace=trace_reduce.from_planes(
        {p: {l: [tuple(s) for s in spans] for l, spans in lines.items()}
         for p, lines in planes.items()}),
        cell=dict(traced["cell"], sizes=dict(
            traced["cell"]["sizes"], program={"architecture": "falcon_h1"})))
    assert harness.read_metric(name, ctx) is None
    assert harness.read_metric(name, dict(ctx, trace=None)) is None


def test_cost_functions_against_hand_counts():
    sizes = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "phi-4-mini-flash.json")))
    assert phi4flash_costs.geometry(sizes) == (5120, 16, 4, 9, 8, 1280)
    # a token: K and V of 20 heads x 64, 2 bytes, in ONE layer
    assert phi4flash_costs.cache_bytes_a_token(sizes) == 5120
    assert phi4flash_costs.shared_decode_bytes(sizes, 1, 128) == \
        128 * 1280 * 2 * 2 == 655360
    # 22 slots of 7.5k tokens, 8 reading layers: the issue's 6.8 GB
    reads = 22 * -(-7500 // 128) * 8
    assert phi4flash_costs.shared_decode_bytes(sizes, reads, 128) == \
        pytest.approx(6.8e9, rel=0.01)
    assert phi4flash_costs.decode_state_traffic_bytes(sizes, 64) == \
        2 * 9 * 64 * 5120 * 16 * 4
    flops, nbytes = phi4flash_costs.prefill_scan_cost(sizes, 512)
    assert flops == 9 * 6 * 512 * 5120 * 16
    assert nbytes == 9 * (512 * 5120 * 8 + 512 * 32 * 2 + 2 * 5120 * 16 * 4)
    # what expanding a chunk in XLA would write: 168 MB an array
    assert 512 * 5120 * 16 * 4 == 167772160


# ----------------------------------------------------------------------
# the files
# ----------------------------------------------------------------------
# the catalog's `config` for Phi-4-mini-flash-reasoning (the
# `model-configs` guide's architectures.jsonl), every key
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


def test_configuration_keeps_every_published_value_and_cuts_nothing():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "phi-4-mini-flash.json")) as f:
        sizes = json.load(f)
    assert {k: sizes[k] for k in PUBLISHED} == PUBLISHED
    assert sizes["reduced"] == [] and "published" not in sizes
    assert sizes["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    assumed = sizes["assumed"]
    assert {k: assumed[k] for k in (
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")} \
        == {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
            "mamba_dt_rank": 160}
    for reasoned in ("mamba", "layer_map", "attention_bias",
                     "differential_attention", "window", "positions",
                     "precision", "weights"):
        assert len(assumed[reasoned]) > 40, reasoned
    assert sizes["program"] == {"architecture": "phi4flash",
                                "param_dtype": "bfloat16"}
    assert "whole" in sizes["deployment"]


def test_the_programs_config_holds_the_published_values():
    import dataclasses
    from deepspeed_tpu.models.phi4flash import Phi4FlashConfig
    cfg = Phi4FlashConfig()
    for f in dataclasses.fields(cfg):
        if f.name in PUBLISHED:
            assert getattr(cfg, f.name) == PUBLISHED[f.name], f.name
    assert (cfg.d_inner, cfg.head_dim, cfg.state_layers, cfg.window_layers,
            cfg.shared_readers, cfg.caching_layers) == (5120, 64, 9, 8, 8, 17)
    # 3.85B parameters, 7.7 GB in bfloat16
    import jax
    from deepspeed_tpu.models import phi4flash
    shapes = jax.eval_shape(lambda k: phi4flash.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert 3.84e9 < n < 3.86e9


def test_the_benchmark_holds_the_cell_by_membership():
    """The configuration, the cell and the four metrics are IN the
    lists (wherever later PRs append theirs), with the files they
    name; every metric that lists the cell has a reader."""
    bench = harness.load_benchmark()
    config = {c["name"]: c for c in bench["configs"]}["phi-4-mini-flash"]
    assert config["reduced"] == [] and config["file"] == \
        "benchmark/configs/phi-4-mini-flash.json"
    assert config["source"] == \
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/" \
        "blob/main/config.json"
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("phi-4-mini-flash", "serve-think-steady", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for rel in (config["file"], "benchmark/traffic/serve-think-steady.json",
                "benchmark/architectures/phi4flash.py",
                "benchmark/weights_phi4flash.py",
                "benchmark/reference/phi4flash.py",
                "benchmark/phi4flash_costs.py"):
        assert os.path.exists(os.path.join(REPO, rel)), rel
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "itl_mean_ms"
        assert m["source"] == "device_trace" and m["unit"] == "%"
    assert per_layer["shared_kv_decode_roofline"]["layer"] == "kernels"
    assert per_layer["mamba1_decode_roofline"]["layer"] == "kernels (ssm)"
    assert BROUGHT_NOT_LISTED not in per_layer
    listed = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])]
    assert {"itl_mean_ms", "serve_tokens_per_s", "decode_iter_ms",
            "attention_time_share.serve", "kv_gather_time_share.serve",
            "ssm_state_time_share.serve",
            "program_temp_gb.serve"} <= set(listed)
    for name in listed + [BROUGHT_NOT_LISTED]:
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", name + ".py")) or \
            name in ("itl_mean_ms", "serve_tokens_per_s"), name
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    assert {per_layer[n]["layer"] for n in NEW} <= layers


def test_the_traffic_file_holds_the_issues_parameters():
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "serve-think-steady.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "serve_open_arch" and mix["chips"] == 1
    inf = mix["inference"]
    assert (inf["max_slots"], inf["prefill_chunk"], inf["sync_every"],
            inf["max_seq_len"], inf["kv_cache"]["page_size"]) == \
        (64, 512, 4, 18432, 128)
    arrivals = mix["arrivals"]
    assert (arrivals["process"], arrivals["schedule_seed"],
            arrivals["seed_jitter_s"]) == ("jittered_grid", 41, 0.4)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 6144,
                                    "sigma": 0.6, "min": 1024, "max": 16384}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.5, "min": 384, "max": 2048}
    assert mix["max_total_tokens"] == 18432 and mix["drain_s"] == 15
    assert mix["tokens"] == {"dist": "uniform"}
    assert set(mix["check"]["limits"]) == {
        "live_logits_rel", "served_gap_max", "served_gap_mean",
        "scan_state_rel", "shared_rows_rel"}
    assert mix["control"] == {"reference_cast": "float8_e4m3fn"}
    assert len(mix["check"]["limits_set_from"]) > 200
    assert len(mix["sized_by"]) > 200
