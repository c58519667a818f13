"""The join of a device profile with the program registry
(`benchmark/scope_reduce.py`) and the readers built on it, on a small
pair made by hand in the shape of a recorded one:
`tiny_scopes_trace.json` (`tiny_trace.json`'s format: one prefill and
one decode launch with a `while` each, and one launch of a program the
registry does not hold) and `tiny_scopes_map.json` ({program:
{instruction: name stack}}); and on a pair recorded on the chip.
Nothing here touches a device."""

import json
import os
import sys

import pytest

import tiny_copy  # noqa: F401  (puts the repo root on sys.path)
from benchmark import harness, scope_reduce, trace_reduce
from deepspeed_tpu.monitor import programs

HERE = os.path.dirname(__file__)
WINDOW = 0.4
# seconds by region, read off the file by hand
CARRY = 0.020 + 0.010 + 0.005 + 0.060 + 0.010   # slices, copy.9, whiles
EXPECTED = {
    "kv_pool_carry_time_share.serve": 100 * CARRY / WINDOW,
    "kv_gather_time_share.serve": 100 * (0.020 + 0.030) / WINDOW,
    "attention_time_share.serve": 100 * 0.020 / WINDOW,
    "weight_matmul_time_share.serve":
        100 * (0.015 + 0.020 + 0.020 + 0.010) / WINDOW,
    "unscoped_time_share.serve": 100 * (0.010 + 0.010) / WINDOW,
}


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "tiny_scopes_trace.json")) as f:
        planes = {p: {l: [tuple(s) for s in spans]
                      for l, spans in lines.items()}
                  for p, lines in json.load(f).items()}
    return trace_reduce.from_planes(planes)


@pytest.fixture(scope="module")
def maps():
    with open(os.path.join(HERE, "tiny_scopes_map.json")) as f:
        return json.load(f)


class FakeCompiled:
    """What the registry asks of a `jax.stages.Compiled`."""

    def __init__(self, scopes, temp=0):
        self.text = "HloModule jit_f, is_scheduled=true\n\n" + "\n".join(
            f'  %{name} = f32[] add(%a, %b), metadata={{op_name="{stack}" '
            f'source_file="engine.py" source_line=1}}'
            for name, stack in scopes.items())
        self.temp = temp

    def as_text(self):
        return self.text

    def memory_analysis(self):
        class Stats:
            argument_size_in_bytes = output_size_in_bytes = 0
            alias_size_in_bytes = generated_code_size_in_bytes = 0
            temp_size_in_bytes = self.temp
        return Stats()


@pytest.fixture
def registered(monkeypatch, maps):
    """The registry holding the pair's two programs, for one test."""
    monkeypatch.setattr(programs, "_programs", {})
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    for name, scopes in maps.items():
        programs.register(name, FakeCompiled(scopes, temp=700_000_000))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_known_answer(name, trace, registered):
    got = harness.read_metric(name, {"trace": trace})
    assert got == pytest.approx(EXPECTED[name], rel=1e-9)


def test_every_region_and_the_sum(trace, maps):
    secs = scope_reduce.region_seconds(trace, maps.get)
    assert secs == pytest.approx({
        "layers": CARRY, "kv_gather": 0.05, "attn": 0.02, "mlp": 0.015,
        "attn_qkv": 0.02, "attn_out": 0.02, "head": 0.01, "kv_write": 0.02,
        "embed": 0.02, "sample": 0.005,
        scope_reduce.NAMED_ELSEWHERE: 0.005, scope_reduce.UNSCOPED: 0.02})
    # every event is in exactly one region: the split is of the busy time
    assert sum(secs.values()) == pytest.approx(
        trace_reduce.busy_seconds(trace))
    assert sum(secs.values()) == pytest.approx(0.31)


def test_while_inheritance(trace, maps):
    """`copy.9` is in no map. Inside prefill's `while` it takes the
    `while`'s name stack (the pool's carry); before decode's `while` it
    is unscoped. With the `while`s out of the maps nothing is left to
    inherit."""
    with_while = scope_reduce.region_seconds(trace, maps.get)
    bare = {p: {k: v for k, v in m.items() if not k.startswith("while")}
            for p, m in maps.items()}
    without = scope_reduce.region_seconds(trace, bare.get)
    # prefill's copy.9 (0.010) and both whiles' self times (0.005, 0.010)
    assert without[scope_reduce.UNSCOPED] - \
        with_while[scope_reduce.UNSCOPED] == pytest.approx(0.025)
    assert with_while["layers"] - without["layers"] == pytest.approx(0.025)


def test_same_instruction_name_in_two_programs(trace, maps):
    """`fusion.2` is `kv_gather` in the prefill program and `attn_qkv`
    in the decode program: an event is looked up in the map of the
    launch that contains it."""
    assert "kv_gather" in maps["jit_prefill_fn"]["fusion.2"]
    assert "attn_qkv" in maps["jit_decode_fn"]["fusion.2"]
    secs = scope_reduce.region_seconds(trace, maps.get)
    assert secs["attn_qkv"] == pytest.approx(0.02)
    assert secs["kv_gather"] == pytest.approx(0.02 + 0.03)
    one = scope_reduce.region_seconds(
        trace, {"jit_decode_fn": maps["jit_decode_fn"]}.get)
    assert one["kv_gather"] == pytest.approx(0.03)


@pytest.mark.parametrize("scopes_of", [
    lambda program: None,
    lambda program: {"fusion.1": "jit(decode_fn)/add",
                     "while.1": "jit(decode_fn)/while"},
], ids=["no-registry", "names-from-before-the-scopes"])
def test_none_without_the_vocabulary(trace, scopes_of):
    assert scope_reduce.region_seconds(trace, scopes_of) is None


def test_readers_none_without_the_vocabulary(trace, monkeypatch):
    """An executable from before the scopes (the parent's, or a cache's)
    is in the registry with other names: no reader reports 0."""
    monkeypatch.setattr(programs, "_programs", {})
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    programs.register("jit_decode_fn", FakeCompiled(
        {"fusion.1": "jit(decode_fn)/add", "while.1": "jit(decode_fn)/while"}))
    for name in EXPECTED:
        assert harness.read_metric(name, {"trace": trace}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED) + ["program_temp_gb.serve"])
def test_readers_none_where_the_program_has_no_registry(name, trace,
                                                        monkeypatch):
    """The parent commit: `deepspeed_tpu.monitor.programs` is not there
    to import. The reader returns nothing and does not raise."""
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.monitor.programs", None)
    monkeypatch.delattr("deepspeed_tpu.monitor.programs", raising=False)
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    assert harness.read_metric(name, {"trace": trace}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_readers_none_without_a_trace(name, registered):
    assert harness.read_metric(name, {"trace": None}) is None


def test_program_temp_reads_the_registry(registered):
    assert harness.read_metric("program_temp_gb.serve", {"trace": None}) == \
        pytest.approx(0.7)
    programs._programs.pop("jit_decode_fn")
    assert harness.read_metric("program_temp_gb.serve",
                               {"trace": None}) is None


def test_regions_are_the_programs_vocabulary():
    from deepspeed_tpu.inference import engine
    assert scope_reduce.REGIONS == engine.SCOPES
    assert scope_reduce.IN_LAYER == engine.SCOPES_IN_LAYER


def test_new_entries_are_appended_and_have_readers():
    bench = harness.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    new = sorted(EXPECTED) + ["program_temp_gb.serve"]
    assert sorted(names[-len(new):]) == sorted(new)
    for m in bench["per_layer"][-len(new):]:
        assert m["workloads"] == ["gpt2-1.5b.serve-chat-steady"]
        assert "roofline" not in m["name"] and "mfu" not in m["name"]


def test_join_on_a_pair_recorded_on_the_chip():
    """`chip_scopes_trace.json` + `chip_scopes_map.json`: a tiny serving
    engine (2 layers) on a TPU v5e under the profiler for one prefill
    chunk and two decode launches, and the `op_scopes` of its two
    programs cut to the instructions that ran (my chip run, PR 24;
    event texts cut to 110 characters). Every region of the
    vocabulary takes time, nothing in a registered program is left
    unscoped, and the split is of the busy time."""
    with open(os.path.join(HERE, "chip_scopes_trace.json")) as f:
        planes = {p: {l: [tuple(s) for s in spans]
                      for l, spans in lines.items()}
                  for p, lines in json.load(f).items()}
    with open(os.path.join(HERE, "chip_scopes_map.json")) as f:
        recorded = json.load(f)
    tr = trace_reduce.from_planes(planes)
    launches = [scope_reduce.program_of(n) for n, _, _ in
                tr.devices["/device:TPU:0"][trace_reduce.MODULES_LINE]]
    assert launches.count("jit_decode_fn") == 2
    assert launches.count("jit_prefill_fn") == 1
    secs = scope_reduce.region_seconds(tr, recorded.get)
    assert all(secs[r] > 0 for r in scope_reduce.REGIONS)
    assert sum(secs.values()) == pytest.approx(
        trace_reduce.busy_seconds(tr), rel=1e-9)
    # the two eager `convert_element_type` launches are no program of
    # the registry's: 0.03 of 90 us
    assert secs[scope_reduce.UNSCOPED] < 1e-3 * sum(secs.values())
    # a while's own name stack ends in `layers/while`; its body's
    # slices carry no inner region
    stacks = set(recorded["jit_decode_fn"].values())
    assert "jit(decode_fn)/layers/while" in stacks
    assert "jit(decode_fn)/layers/while/body/dynamic_slice" in stacks


def test_a_traced_tiny_serving_run_reads_the_real_registry(tmp_path,
                                                           monkeypatch):
    """The whole path on the CPU: the harness builds a real engine,
    which registers its programs; after the run (the engine deleted)
    `program_temp_gb.serve` reads the compiler's count from the real
    `Compiled`. The CPU's profile has no device plane, so the shares
    have nothing to read and are left out of the line."""
    import time
    h = tiny_copy.point_harness_at(monkeypatch, tiny_copy.make(tmp_path))
    monkeypatch.setattr(scope_reduce, "_last", (None, None))
    result = h.run_cell("tiny.tiny-serve", 2**31 + 24, 2.0, 1, time.time(),
                        need_tpu=False)
    assert result["correct"]
    temp = result["metrics"]["program_temp_gb.serve"]
    assert temp["unit"] == "GB" and temp["value"] == pytest.approx(
        programs.memory("jit_decode_fn")["temp"] / 1e9)
    assert temp["value"] > 0
    assert not set(EXPECTED) & set(result["metrics"])
