"""What ISSUE 37 adds to the benchmark: `benchmark/host_phases.py` over
the program's ring of serving spans and the traced tail (the clock's
alignment, the split of the device's idle gaps by the phase the host
was in, on spans and traces made by hand), its five readers on tiny
CPU runs of a GPT-2 cell and an architecture cell, and the files."""

import os
import time

import pytest

import tiny_brumby
import tiny_copy
from benchmark import (harness, host_phases, region_join, scope_reduce,
                       state_scopes, trace_reduce)
from deepspeed_tpu.monitor import trace as program_trace

SEED = 2**31 + 137
REPO = tiny_copy.REPO
NEW = ("host_iter_ms.serve", "host_exposed_ms.serve",
       "readback_exposed_ms.serve", "bookkeeping_exposed_ms.serve",
       "dispatch_exposed_ms.serve")
SERVING = ["gpt2-1.5b.serve-chat-steady", "brumby-14b.serve-longdoc-steady",
           "falcon-h1-34b.serve-longctx-steady",
           "trinity-mini.serve-reason-steady"]
PARENT_PER_LAYER = 34
PARENT_NAMES = [
    "cache_misses_setup", "compiles_in_window.train",
    "compiles_in_window.serve", "ttft_observed_mean_ms", "ttft_p90_ms",
    "itl_p95_ms", "step_ms", "train_mfu", "peak_hbm_gb.train",
    "peak_hbm_gb.serve", "pallas_time_share.train", "flash_roofline",
    "decode_iter_ms", "prefill_chunk_ms", "queue_wait_mean_ms",
    "slots_occupied_mean", "device_idle_share.train",
    "device_idle_share.serve", "kv_pool_carry_time_share.serve",
    "kv_gather_time_share.serve", "attention_time_share.serve",
    "weight_matmul_time_share.serve", "unscoped_time_share.serve",
    "program_temp_gb.serve", "retention_state_time_share.serve",
    "retention_decode_roofline", "retention_prefill_roofline",
    "state_resident_gb.serve", "ssm_state_time_share.serve",
    "ssm_decode_roofline", "moe_time_share.serve", "moe_expert_roofline",
    "moe_experts_touched_share", "kv_window_resident_share.serve"]
OFFSET = -1000.25            # the profile's clock starts with the profiler


def span(it, phase, t0, dt, loop_s=None, loop=7):
    return (loop, it, phase, t0, dt, 60.0 + it if loop_s is None else loop_s)


def iteration(it, t0, activate=False):
    """One iteration of 10 ms from `t0` on the ring's clock: admit 1,
    (activate 2 = 0.5 + 1.5), pages 1, dispatch 1, device_get 4,
    bookkeeping 1 + (2 or nothing)."""
    out = [span(it, "admit", t0, 1e-3)]
    t = t0 + 1e-3
    if activate:
        out += [span(it, "activate", t, 2e-3),
                span(it, "activate.first_update", t, 0.5e-3),
                span(it, "activate.other_updates", t + 0.5e-3, 1.5e-3)]
        t += 2e-3
    out += [span(it, "decode.pages", t, 1e-3),
            span(it, "decode.dispatch", t + 1e-3, 1e-3),
            span(it, "fence.device_get", t + 2e-3, 4e-3),
            span(it, "fence.bookkeeping", t + 6e-3, 1e-5),
            span(it, "fence.bookkeeping", t + 6e-3 + 1e-5,
                 t0 + 10e-3 - (t + 6e-3 + 1e-5))]
    return out


def made_by_hand(moved=0.0):
    """Three iterations on the ring's clock from 2000.0 s, an idle
    stretch of 5 ms before the third, and the profile of their tail:
    `bench/fence` 20 us round every `fence.device_get` (the second one
    moved by `moved` s), the device busy from each dispatch's end to 1
    ms before each `device_get` returns."""
    ring = iteration(1, 2000.0) + iteration(2, 2000.010, activate=True) + \
        [span(3, "idle", 2000.020, 5e-3)] + iteration(3, 2000.025)
    gets = [s for s in ring if s[2] == "fence.device_get"]
    host = [("bench/fence", g[3] + OFFSET - 10e-6 + (moved if i == 1 else 0),
             g[3] + g[4] + OFFSET + 10e-6 + (moved if i == 1 else 0))
            for i, g in enumerate(gets)]
    ops = [("%fusion.1 = f32[] fusion()", g[3] + OFFSET,
            g[3] + g[4] + OFFSET - 1e-3) for g in gets]
    trace = trace_reduce.Trace(
        {"/device:TPU:0": {trace_reduce.OPS_LINE: ops}}, host,
        (2000.0 + OFFSET, 2000.035 + OFFSET))
    return ring, trace


# ----------------------------------------------------------------------
# the clock
# ----------------------------------------------------------------------
def test_align_finds_the_offset_and_the_miss_of_a_moved_span():
    ring, trace = made_by_hand()
    offset, miss, k = host_phases.align(trace, ring)
    # the wrapper opens 10 us before the get: the median difference of
    # the starts, which every mapped get then leaves by nothing
    assert k == 3 and offset == pytest.approx(OFFSET - 10e-6, abs=1e-9)
    assert miss == pytest.approx(0.0, abs=1e-9)
    ring, trace = made_by_hand(moved=0.5e-3)
    offset, miss, k = host_phases.align(trace, ring)
    assert offset == pytest.approx(OFFSET - 10e-6, abs=1e-9)
    assert miss == pytest.approx(0.5e-3, abs=1e-8)
    assert "OVER 0.2 ms" in host_phases.line({
        "window": host_phases.window(ring, 3),
        "tail": host_phases.tail(trace, ring)})
    # spans of loops that ran before in this process are not the run's
    older = [span(1, "fence.device_get", 10.0, 1.0, loop=3)] * 5
    assert host_phases.align(trace, older + ring)[2] == 3
    # a tail with no fence in it
    empty = trace_reduce.Trace(trace.devices, [], trace.window)
    assert host_phases.align(empty, ring) == (None, None, 0)


# ----------------------------------------------------------------------
# the split
# ----------------------------------------------------------------------
EXPOSED_S = {
    # a gap runs from 1 ms before a get returns to the next dispatch's
    # end (the first from the window's start, the last to its end):
    # under the get 1 ms x 3
    "fence.device_get": 3e-3,
    # the bookkeeping whole, 3 + 1 + 3, admit 1 x 3, pages 1 x 3
    "fence.bookkeeping": 7e-3, "admit": 3e-3, "decode.pages": 3e-3,
    "decode.dispatch": 3e-3,
    # the parent's span less its children's is nothing here
    "activate.first_update": 0.5e-3, "activate.other_updates": 1.5e-3,
    "idle": 5e-3,
    # the offset is the wrapper's 10 us early: so much of the window's
    # end lies past the last mapped span
    host_phases.NO_PHASE: 1e-5,
}


def test_a_gap_goes_to_every_phase_it_overlaps_by_the_overlap():
    ring, trace = made_by_hand()
    tail = host_phases.tail(trace, ring)
    assert tail["iterations"] == 3
    assert set(tail["exposed_s"]) == set(EXPOSED_S)
    for phase, want in EXPOSED_S.items():
        assert tail["exposed_s"][phase] == pytest.approx(want, abs=2e-5), \
            phase
    # one gap over three phases and more: `trace_reduce` gives it whole
    # to the span in which it began
    assert set(trace_reduce.idle_gaps(trace)) == {"bench/fence",
                                                  "(no span)"}
    # the gaps are all there: the window less the busy time
    assert sum(tail["exposed_s"].values()) == pytest.approx(
        trace_reduce.window_seconds(trace) -
        trace_reduce.busy_seconds(trace), abs=1e-9)


@pytest.mark.parametrize("phases, want_ms", [
    (None, (35 - 9 - 5) / 3),          # the window less busy, less idle
    (host_phases.READBACK, 3 / 3),
    (host_phases.BOOKKEEPING, (7 + 3 + 3) / 3),
    (host_phases.DISPATCH, (3 + 0.5 + 1.5) / 3),
    (("idle",), 5 / 3)])
def test_the_parts_and_what_stays_out(phases, want_ms):
    """The three parts sum to `host_exposed_ms.serve`; the wait for an
    arrival is in none of them."""
    ring, trace = made_by_hand()
    tail = host_phases.tail(trace, ring)
    assert host_phases.exposed_ms(tail, phases) == pytest.approx(
        want_ms, abs=0.02)
    assert host_phases.exposed_ms(tail) == pytest.approx(sum(
        host_phases.exposed_ms(tail, part) for part in (
            host_phases.READBACK, host_phases.BOOKKEEPING,
            host_phases.DISPATCH)), rel=1e-9)
    assert "idle" not in (host_phases.READBACK + host_phases.BOOKKEEPING +
                          host_phases.DISPATCH)


def test_the_window_on_the_hosts_clock():
    ring, _ = made_by_hand()
    before = [span(0, "fence.device_get", 1990.0, 5.0, loop_s=-1.0)]
    win = host_phases.window(before + ring, fences=2)
    assert win["iterations"] == 2
    assert win["ms"]["fence.device_get"] == pytest.approx(4.0)
    assert win["ms"].get("activate", 0.0) == pytest.approx(0.0, abs=1e-9)
    assert win["ms"]["activate.other_updates"] == pytest.approx(0.75)
    # two iterations of 10 ms, the get's 4 ms out of each
    assert host_phases.host_iter_ms(win) == pytest.approx(6.0)
    assert win["longest"] == pytest.approx(("fence.device_get", 4.0, 61.0))
    # the third iteration brings the wait for its arrival, under `idle`
    win = host_phases.window(ring, fences=12)
    assert win["iterations"] == 3
    assert win["ms"]["idle"] == pytest.approx(5.0 / 3)
    assert host_phases.host_iter_ms(win) == pytest.approx(6.0)
    assert win["longest"][0] == "fence.device_get"
    assert host_phases.window(ring, fences=0)["iterations"] == 0


def test_the_vocabulary_is_the_programs():
    assert host_phases.PHASES == program_trace.SERVE_PHASES
    parts = host_phases.READBACK + host_phases.BOOKKEEPING + \
        host_phases.DISPATCH
    assert sorted(parts + ("idle",)) == sorted(
        host_phases.PHASES + (host_phases.NO_PHASE,))


def test_a_program_without_the_ring_reads_none(monkeypatch):
    """The parent commit: the readers find nothing and do not raise."""
    monkeypatch.delattr(program_trace, "recent_spans")
    ctx = {"trace": None, "fences_in_window": 3}
    assert host_phases.of_run(ctx) is None
    assert [harness.read_metric(name, ctx) for name in NEW] == [None] * 5


# ----------------------------------------------------------------------
# the readers on tiny runs
# ----------------------------------------------------------------------
CELLS = {"gpt2": "tiny.tiny-serve", "brumby": tiny_brumby.CELL}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of a tiny GPT-2 cell and one of a tiny
    architecture cell, with what each printed."""
    out = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(scope_reduce, "_last", (None, None))
        patch.setattr(state_scopes, "_last", (None, None))
        patch.setattr(region_join, "_last", (None, None, None))
        patch.setattr(host_phases, "_last", (None, None))
        said = []
        patch.setattr(host_phases, "say", lambda *parts: said.append(parts))
        h = tiny_copy.point_harness_at(patch, tiny_brumby.make(
            tmp_path_factory.mktemp("host_phases")))
        for family, cell in CELLS.items():
            del said[:]
            result = h.run_cell(cell, SEED, 2.0, 1, time.time(),
                                need_tpu=False, keep_checks=True)
            out[family] = (result, list(said),
                           host_phases.of_last_loop(host_phases.ring()))
    return out


@pytest.mark.parametrize("family", sorted(CELLS))
@pytest.mark.parametrize("metric", NEW)
def test_every_reader_gives_a_number_on_a_tiny_run(traced, family, metric):
    """No device plane on the CPU: nothing exposed reads 0.0, never
    None; the host's own milliseconds are read off the ring."""
    result, said, ring = traced[family]
    assert result["correct"], result["checks"]
    got = result["metrics"][metric]
    assert got["unit"] == "ms" and isinstance(got["value"], float)
    if metric == NEW[0]:
        assert 0.0 < got["value"] < 1e3
    else:
        assert got["value"] == 0.0
    # the line once a traced run, and the run's ring in bounds
    assert len(said) == 1 and said[0][0] == "host phases:"
    assert "fence.device_get" in said[0][1] and "idle" in said[0][1]
    assert 0 < len(ring) < 25000
    assert {s[2] for s in ring} <= set(host_phases.PHASES)


# ----------------------------------------------------------------------
# the files
# ----------------------------------------------------------------------
def test_the_metrics_are_appended():
    bench = harness.load_benchmark()
    mine = bench["per_layer"][PARENT_PER_LAYER:PARENT_PER_LAYER + 5]
    assert [m["name"] for m in mine] == list(NEW)
    assert not set(NEW) & {m["name"]
                           for m in bench["per_layer"][:PARENT_PER_LAYER]}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["better"], m["layer"], m["moves"]) == (
            "ms", "lower", "serving loop, scheduler", "itl_mean_ms")
        assert m["workloads"][:4] == SERVING
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".py"))
    assert [m["source"] for m in mine] == ["host_clock"] + \
        ["device_trace"] * 4
    # every cell that reports the end-to-end metric they move
    itl = next(m for m in bench["end_to_end"] if m["name"] == "itl_mean_ms")
    assert itl["workloads"][:4] == SERVING
    # the parent's metrics stand before them, in the parent's order
    assert [m["name"] for m in bench["per_layer"][:PARENT_PER_LAYER]] == \
        PARENT_NAMES
