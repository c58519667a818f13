"""The serving programs' named regions and the program registry
(ISSUE 24): `deepspeed_tpu/monitor/programs.py`, the `SCOPE_*`
vocabulary of `deepspeed_tpu/inference/engine.py`, the inner profiler
spans of the two host calls that own the serving cell's idle gaps, and
TTFT counted from arrival. CPU, tiny engine."""

import contextlib
import gc
import json
import os

import numpy as np
import pytest

import jax

from deepspeed_tpu.inference import (InferenceEngine, Request, ServingLoop)
from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config
from deepspeed_tpu.monitor import programs
from deepspeed_tpu.monitor import trace as trace_mod

INFERENCE = {"max_slots": 4, "prefill_chunk": 16, "sync_every": 4,
             "max_new_tokens": 32,
             "kv_cache": {"num_pages": 120, "page_size": 4}}


@pytest.fixture(scope="module")
def model_and_params():
    cfg = tiny_gpt2_config()
    model = GPT2ForCausalLM(cfg)
    return cfg, model.init(jax.random.PRNGKey(0),
                           {"input_ids": np.zeros((1, 8), np.int32)})


def build(model_and_params, **extra):
    cfg, params = model_and_params
    return InferenceEngine(cfg, params, dict(extra, inference=INFERENCE))


@pytest.fixture
def engine(model_and_params):
    return build(model_and_params)


def regions(name_stack):
    return [p for p in name_stack.split("/") if p in engine_mod.SCOPES]


# ----------------------------------------------------------------------
# the vocabulary in the compiled programs
# ----------------------------------------------------------------------
@pytest.mark.parametrize("program, expected", [
    # decode gathers no window: its attention is the kernel that reads
    # the pages where they lie, under `attn` (ISSUE 27)
    ("jit_decode_fn", tuple(s for s in engine_mod.SCOPES
                            if s != engine_mod.SCOPE_KV_GATHER)),
    # prefill stops at the pools: no head, no sampling, no slot state
    ("jit_prefill_fn", (engine_mod.SCOPE_EMBED, engine_mod.SCOPE_LAYERS) +
     engine_mod.SCOPES_IN_LAYER),
])
def test_every_region_is_in_the_programs_map(engine, program, expected):
    scopes = programs.op_scopes(program)
    found = {r for stack in scopes.values() for r in regions(stack)}
    assert found == set(expected)
    # every inner region lies inside the scan, and nothing else does
    # (a reduction's own small computation has a relative name stack)
    for stack in scopes.values():
        rs = regions(stack)
        if stack.startswith("jit(") and rs and \
                rs[-1] in engine_mod.SCOPES_IN_LAYER:
            assert rs == [engine_mod.SCOPE_LAYERS, rs[-1]], stack


@pytest.mark.parametrize("program", ["jit_decode_fn", "jit_prefill_fn"])
def test_the_scans_own_slices_carry_no_inner_region(engine, program):
    """`layers` is round the `lax.scan` call and nothing else, so the
    slicing of its xs (the stacked weights, the layer index) reads
    `.../layers/while/body/dynamic_slice`. The scan has no ys since
    the page pools ride in its carry (ISSUE 25), so nothing is written
    back there: a `dynamic_update_slice` under `layers` alone would be
    a pool (or anything else) stacked layer by layer again."""
    stacks = set(programs.op_scopes(program).values())
    jitted = "jit(" + program[len("jit_"):] + ")"
    assert f"{jitted}/layers/while/body/dynamic_slice" in stacks
    assert f"{jitted}/layers/while/body/dynamic_update_slice" not in stacks
    assert f"{jitted}/layers/while" in stacks


def test_parse_op_scopes_of_hlo_text():
    text = """HloModule jit_f, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/layers/while/body/closed_call/mlp/add" source_file="a.py" source_line=3}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %copy.27 = f32[4]{0} copy(%x)
  %c = s32[] constant(0)
  %copy.28 = s32[] copy(%c)
  fusion.2 = f32[4]{0} fusion(%copy.27), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/layers/while/body/closed_call/mlp/add" source_file="a.py" source_line=3}
  ROOT %dynamic-slice.3 = f32[2]{0} dynamic-slice(fusion.2, %c), dynamic_slice_sizes={2}, metadata={source_file="a.py" op_name="jit(f)/layers/while/body/dynamic_slice"}
}
"""
    assert programs.parse_op_scopes(text) == {
        "add.1": "jit(f)/layers/while/body/closed_call/mlp/add",
        "x": "x",
        # the compiler's copy of a named value is that value's; of an
        # unnamed one, nobody's
        "copy.27": "x",
        "fusion.2": "jit(f)/layers/while/body/closed_call/mlp/add",
        "dynamic-slice.3": "jit(f)/layers/while/body/dynamic_slice"}


def test_parse_relaid_of_hlo_text():
    """The weights a program writes only to hold them in another
    arrangement: the copy of a layer's slice of a scan's stacked
    operand, and the copy (through the compiler's own bitcast) of a
    leaf of `params`; not a prefetch, not another argument's copy, not
    a copy inside a product's fusion."""
    text = """HloModule jit_f, is_scheduled=true

%fused_computation (p: bf16[96,64,192]) -> bf16[96,64,192] {
  %p = bf16[96,64,192]{2,1,0} parameter(0)
  ROOT %copy.139 = bf16[96,64,192]{2,1,0:T(8,128)(2,1)} copy(%p), metadata={op_name="jit(f)/layers/while/body/closed_call/attn_qkv/dot_general"}
}

%body (c: (s32[], bf16[4,8,16])) -> (s32[], bf16[4,8,16]) {
  %c = (s32[], bf16[4,8,16]{2,1,0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %w = bf16[4,8,16]{2,1,0} get-tuple-element(%c), index=1
  %constant_dynamic-slice_fusion.8 = bf16[1,8,16]{2,1,0:T(8,128)(2,1)} fusion(%w, %i), kind=kLoop, calls=%fused_slice, metadata={op_name="jit(f)/layers/while/body/dynamic_slice"}
  %copy.177 = bf16[1,8,16]{1,2,0:T(8,128)(2,1)S(1)} copy(%constant_dynamic-slice_fusion.8), metadata={op_name="jit(f)/layers/while/body/dynamic_slice" stack_frame_id=10}, backend_config={"window_config":{"kernel_window_bounds":[]}}
  %copy.178 = f32[2,3]{1,0:T(8,128)S(1)} copy(%act), metadata={op_name="jit(f)/layers/while/body/closed_call/attn_out/convert_element_type"}
  ROOT %t = (s32[], bf16[4,8,16]{2,1,0}) tuple(%i, %w)
}

ENTRY %main (wq: bf16[1,8,32], tables: s32[6,4]) -> bf16[8,32] {
  %params__dense____wq__.1 = bf16[1,8,32]{2,1,0} parameter(0), metadata={op_name="params[\\'dense\\'][\\'wq\\']"}
  %tables.1 = s32[6,4]{1,0} parameter(1), metadata={op_name="tables"}
  %copy.93 = s32[6,4]{1,0:T(8,128)S(1)} copy(%tables.1), metadata={op_name="tables"}
  %bitcast.663 = bf16[32,8]{0,1:T(8,128)(2,1)} bitcast(%params__dense____wq__.1)
  %copy.172 = bf16[32,8]{1,0:T(8,128)(2,1)S(1)} copy(%bitcast.663), backend_config={"flag_configs":[]}
  %copy-start.1 = (bf16[1,8,32]{2,1,0:S(1)}, bf16[1,8,32]{2,1,0}, u32[]) copy-start(%params__dense____wq__.1)
  ROOT %copy-done.1 = bf16[1,8,32]{2,1,0:S(1)} copy-done(%copy-start.1)
}
"""
    assert programs.parse_relaid(text) == (8 * 16 + 32 * 8) * 2
    assert programs.parse_relaid(text.replace("copy(", "add(")) == 0


# ----------------------------------------------------------------------
# the registry
# ----------------------------------------------------------------------
def test_memory_and_the_operators_table(engine):
    for name in ("jit_decode_fn", "jit_prefill_fn"):
        memory = programs.memory(name)
        assert set(memory) == set(programs.MEMORY_FIELDS)
        assert memory["temp"] > 0 and memory["argument"] > 0
    rows = {r["name"]: r for r in programs.programs()}
    assert rows["jit_decode_fn"]["temp"] == \
        programs.memory("jit_decode_fn")["temp"]
    assert rows["jit_prefill_fn"]["compile_seconds"] > 0
    assert rows["jit_prefill_fn"]["relaid"] == 0
    assert programs.op_scopes("jit_no_such_fn") is None
    assert programs.memory("jit_no_such_fn") is None
    # XLA:CPU re-lays no weight of GPT-2's
    assert programs.relaid("jit_decode_fn") == 0
    assert programs.relaid("jit_no_such_fn") is None


def test_the_registry_keeps_no_device_array(model_and_params):
    """`del engine` frees the pools and the weights' copies although
    the registry still holds, and still answers for, its programs."""
    cfg, params = model_and_params
    held = {id(a) for a in jax.live_arrays()}
    eng = build(model_and_params)
    eng.start_request(0, np.arange(5, dtype=np.int32), max_new=4)
    eng.decode_block(2)
    eng.fetch_state()
    pool_shape = eng._state["k_pool"].shape
    assert any(a.shape == pool_shape for a in jax.live_arrays())
    del eng
    gc.collect()
    left = [a for a in jax.live_arrays() if id(a) not in held]
    assert not any(a.shape == pool_shape for a in left)
    assert sum(a.nbytes for a in left) == 0, [a.shape for a in left]
    assert programs.op_scopes("jit_decode_fn")
    assert programs.memory("jit_decode_fn")["temp"] > 0


def test_an_untraced_run_asks_the_executables_nothing(model_and_params,
                                                      monkeypatch):
    """With no reader, the registry costs one dict insert per program
    built: no `as_text()`, no `memory_analysis()`, through engine
    construction and a served batch."""
    asked = []
    compiled_cls = jax.stages.Compiled
    for method in ("as_text", "memory_analysis", "cost_analysis"):
        inner = getattr(compiled_cls, method)
        monkeypatch.setattr(
            compiled_cls, method,
            lambda self, *a, _m=method, _inner=inner, **k:
            (asked.append(_m), _inner(self, *a, **k))[1])
    eng = build(model_and_params)
    rng = np.random.RandomState(3)
    results = ServingLoop(eng).serve(
        [Request(rid=i, tokens=rng.randint(0, 100, size=6 + i),
                 max_new_tokens=5) for i in range(5)])
    assert len(results) == 5 and asked == []
    assert programs.op_scopes("jit_decode_fn")
    assert asked == ["as_text"]
    programs.op_scopes("jit_decode_fn")       # answered once, then kept
    assert asked == ["as_text"]


def test_name_stacks_are_in_the_cache_key_of_registered_programs(
        model_and_params, monkeypatch):
    """A persistent cache keyed without names hands a build WITH the
    scopes the executables of a build without them (seen on the chip
    with the parent's cache). For the programs the registry reads
    names out of, names are in the key, and only for those."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    seen = []
    real = engine_mod.compile_fresh

    def recording(lowered):
        seen.append(getattr(jax.config, flag))
        return real(lowered)
    monkeypatch.setattr(engine_mod, "compile_fresh", recording)
    assert getattr(jax.config, flag) is False
    build(model_and_params)
    assert seen == [True, True]               # decode, prefill
    assert getattr(jax.config, flag) is False


class NoScope(contextlib.ContextDecorator):
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_decode_logits_bitequal_with_and_without_the_scopes(
        model_and_params, monkeypatch):
    """Named scopes are metadata: the decode program built without any
    (one `compile_fresh` each) gives the same logits bit for bit."""
    def logits_of(eng):
        eng.start_request(0, np.arange(3, 10, dtype=np.int32), max_new=6)
        eng.start_request(2, np.arange(20, 31, dtype=np.int32), max_new=6)
        return [np.asarray(eng.decode_once()) for _ in range(4)]

    with_scopes = logits_of(build(model_and_params))
    assert any(regions(s) for s in
               programs.op_scopes("jit_decode_fn").values())
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", NoScope)
        bare = build(model_and_params)
    assert not any(regions(s) for s in
                   programs.op_scopes("jit_decode_fn").values())
    for a, b in zip(with_scopes, logits_of(bare)):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------
# the host calls' inner spans
# ----------------------------------------------------------------------
def test_fence_and_activate_put_inner_spans_on_the_profilers_clock(
        engine, monkeypatch):
    seen = []

    class Recording:
        def __init__(self, name, **args):
            self.name = name

        def __enter__(self):
            seen.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod, "_TRACE_ANNOTATION", Recording)
    engine.start_request(1, np.arange(6, dtype=np.int32), max_new=3)
    engine.decode_block(1)
    engine.fetch_state()
    # among the phases round them (ISSUE 37: `activate` holds the
    # first two, `fence.bookkeeping` follows the third)
    assert set(seen) <= {"ds_tpu/serve/" + p
                         for p in trace_mod.SERVE_PHASES}
    inner = ("activate.first_update", "activate.other_updates",
             "fence.device_get")
    assert [n for n in seen if n.endswith(inner)] == [
        "ds_tpu/serve/activate.first_update",
        "ds_tpu/serve/activate.other_updates",
        "ds_tpu/serve/fence.device_get"]
    # with no profiler API at all the calls still work
    monkeypatch.setattr(trace_mod, "_TRACE_ANNOTATION", False)
    engine.fetch_state()


# ----------------------------------------------------------------------
# TTFT counts from arrival
# ----------------------------------------------------------------------
def test_ttft_counts_from_arrival_not_admission(model_and_params, tmp_path):
    """One slot, three requests due at once: the second and third wait
    in the queue for the whole of their predecessors, and the client's
    time to first token holds that wait."""
    cfg, params = model_and_params
    eng = InferenceEngine(cfg, params, {
        "inference": dict(INFERENCE, max_slots=1),
        "monitor": {"enabled": True, "sinks": ["jsonl"],
                    "output_path": str(tmp_path)}})
    rng = np.random.RandomState(7)
    reqs = [Request(rid=f"r{i}", tokens=rng.randint(0, 100, size=9),
                    max_new_tokens=8) for i in range(3)]
    ServingLoop(eng).serve(reqs)
    eng.monitor.close()
    finished = {}
    for root, _, files in os.walk(tmp_path):
        for f in (f for f in files if f.endswith(".jsonl")):
            with open(os.path.join(root, f)) as fh:
                for e in map(json.loads, fh):
                    if e["kind"] == "request_finished":
                        finished[e["request_id"]] = e
    assert set(finished) == {"r0", "r1", "r2"}
    for req in reqs:
        row = finished[str(req.rid)]
        assert row["ttft_ms"] == pytest.approx(
            (req.first_token_at - req.arrival_time) * 1e3, abs=1e-3)
        assert row["ttft_ms"] >= row["queued_ms"]
    assert finished["r2"]["queued_ms"] > finished["r1"]["queued_ms"] > 0
    assert finished["r2"]["ttft_ms"] > finished["r1"]["ttft_ms"]
    # the tracker's histogram counts the same way (perf_counter clock)
    trk = eng.tracker
    assert trk.hist_ttft_ms.to_event()["sum_ms"] >= \
        trk.hist_queue_ms.to_event()["sum_ms"] > 0
