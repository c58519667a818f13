"""The serving loop times its own iteration (ISSUE 37): the closed
vocabulary of host phases (`monitor/trace.py::SERVE_PHASES`), their
three readers (the `decode_batch` fence row, the profiler's clock, the
process-wide ring) and what a span costs with nobody listening. CPU,
tiny engine."""

import collections
import gc
import glob
import json
import statistics
import time

import numpy as np
import pytest

import jax

from deepspeed_tpu.inference import InferenceEngine, Request, ServingLoop
from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config
from deepspeed_tpu.monitor import trace as trace_mod
from deepspeed_tpu.monitor.trace import SERVE_PHASES, StepTrace

INFERENCE = {"max_slots": 4, "prefill_chunk": 16, "sync_every": 4,
             "max_new_tokens": 32,
             "kv_cache": {"num_pages": 120, "page_size": 4}}
CHILDREN = {"activate": ("activate.first_update", "activate.other_updates")}


@pytest.fixture(scope="module")
def model_and_params():
    cfg = tiny_gpt2_config()
    model = GPT2ForCausalLM(cfg)
    return cfg, model.init(jax.random.PRNGKey(0),
                           {"input_ids": np.zeros((1, 8), np.int32)})


@pytest.fixture
def no_collections():
    """A collection of the interpreter that lands between two phases
    is no phase's: the tests that hold sums of phases to a wall time
    run with the collector off."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


def build(model_and_params, inference=INFERENCE, **extra):
    cfg, params = model_and_params
    return InferenceEngine(cfg, params, dict(extra, inference=inference))


class Rows:
    """A sink of the test's own: the loop's `decode_batch` rows."""
    name = "test_rows"

    def __init__(self):
        self.rows = []

    def emit(self, event):
        if event["kind"] == "decode_batch":
            self.rows.append(event)

    def flush(self):
        pass

    close = flush


def request(rid, n=25, new=6, at=0.0):
    """25 prompt tokens: two prefill chunks of 16."""
    return Request(rid=rid, tokens=np.arange(n, dtype=np.int32) % 50,
                   max_new_tokens=new, arrival_time=at)


def spans_of(loop):
    return [s for s in trace_mod.recent_spans() if s[0] == loop._id]


def self_times(spans):
    """{phase: self seconds} of one iteration's ring entries."""
    total = collections.Counter()
    for _, _, phase, _, dt, _ in spans:
        total[phase] += dt
    for parent, children in CHILDREN.items():
        total[parent] -= sum(total[c] for c in children)
    return total


def test_every_emitted_name_is_a_member_and_every_member_is_emitted(
        model_and_params, monkeypatch):
    """A run that waits, admits, prefills in two chunks, activates,
    decodes and finishes."""
    seen = []

    class Recording:
        def __init__(self, name, **args):
            seen.append((name, args))

        def __enter__(self):
            pass

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod, "_TRACE_ANNOTATION", Recording)
    engine = build(model_and_params)
    loop = ServingLoop(engine)
    results = loop.serve([request("a"), request("b", at=0.5)])
    assert len(results) == 2
    names = {n for n, _ in seen}
    assert names == {"ds_tpu/serve/" + p for p in SERVE_PHASES}
    ring = spans_of(loop)
    assert {s[2] for s in ring} == set(SERVE_PHASES)
    # every span of an iteration carries the loop's id and its number;
    # prefill's and activate's carry the slot
    for name, args in seen:
        phase = name[len("ds_tpu/serve/"):]
        assert args["loop"] == loop._id
        if phase != "idle":
            assert 1 <= args["iteration"] <= loop._iteration
        if phase.startswith("prefill.") or phase == "activate":
            assert args["slot"] in range(INFERENCE["max_slots"])
    chunks = [a for n, a in seen if n.endswith("prefill.dispatch")]
    assert [(a["start"], a["end"]) for a in chunks
            if a["slot"] == chunks[0]["slot"]][:2] == [(0, 16), (16, 24)]
    # the iterations' fences are on the loop's clock, in order
    fences = [s[5] for s in ring if s[2] == "fence.device_get"]
    assert fences == sorted(fences) and fences[0] > 0


def stepped_through(model_and_params):
    """{iteration: wall seconds of its `step()`} and its ring entries,
    of a run of three requests in blocks of eight launches: iterations
    of several milliseconds, of which the two ends outside every phase
    (the poll's look for work, the ring's append) are well under 2%."""
    engine = build(model_and_params, dict(INFERENCE, sync_every=8))
    loop = ServingLoop(engine)
    for i in range(3):
        loop.submit(request(i, new=30))
    loop._t0, loop._last_fence_t = time.monotonic(), 0.0
    walls = {}
    while loop.queue or loop.live or loop.prefilling:
        t0 = time.perf_counter()
        assert loop.step()
        walls[loop._iteration] = time.perf_counter() - t0
    by_iteration = collections.defaultdict(list)
    for s in spans_of(loop):
        by_iteration[s[1]].append(s)
    return walls, by_iteration


def test_an_iterations_self_times_sum_to_its_wall_time(model_and_params,
                                                       no_collections):
    # a time slice lost between two phases is no phase's either: the
    # best of three runs, on a machine that runs other tests beside
    # this one
    for attempt in range(3):
        walls, by_iteration = stepped_through(model_and_params)
        assert len(walls) >= 4 and set(by_iteration) == set(walls)
        if all(abs(wall - sum(self_times(by_iteration[n]).values()))
               <= max(0.02 * wall, 50e-6) for n, wall in walls.items()):
            break
    for n, wall in walls.items():
        own = self_times(by_iteration[n])
        assert all(v >= 0 for v in own.values()), own
        assert sum(own.values()) == pytest.approx(
            wall, rel=0.02, abs=50e-6), (n, own, wall)
        # no phase of one iteration overlaps another but its parent
        flat = sorted((t0, t0 + dt, p) for _, _, p, t0, dt, _
                      in by_iteration[n]
                      if p not in CHILDREN["activate"])
        assert all(a[1] <= b[0] + 1e-9 for a, b in zip(flat, flat[1:]))


def test_polls_with_nothing_due_leave_one_idle_span_and_no_admit(
        model_and_params):
    engine = build(model_and_params)
    loop = ServingLoop(engine)
    loop.submit(request("late", at=3600.0))
    loop._t0, loop._last_fence_t = time.monotonic(), 0.0
    for _ in range(200):
        assert not loop.step()
    assert spans_of(loop) == []          # the stretch is still open
    assert engine.monitor.trace.drain() == {}
    loop.queue[0].arrival_time = 0.0
    assert loop.step()
    phases = [s[2] for s in spans_of(loop)]
    assert phases.count("idle") == 1 and phases[0] == "idle"
    assert phases.count("admit") == 1
    idle = spans_of(loop)[0]
    assert idle[1] == 1                  # of the iteration that ended it


def test_fence_rows_say_where_the_hosts_time_went(model_and_params,
                                                  no_collections):
    engine = build(model_and_params)
    sink = Rows()
    engine.monitor.attach_sink(sink)
    loop = ServingLoop(engine)
    loop.serve([request(i, new=30, at=0.01 * i) for i in range(9)] +
               [request("late", new=30, at=1.0)])
    rows = sink.rows
    assert len(rows) >= 20
    # Row by row the phases are compared with the HOST's clock, and a
    # host that five other workers load takes the processor away for
    # milliseconds at a time. Inside an iteration that is some phase's
    # time (a span begins where the one before it ended). It is no
    # phase's between two iterations (that row's phases fall short of
    # its window), and between the opening of `fence.bookkeeping` and
    # the reading of the window's end inside it (the row falls short
    # and the next, which reports that span, overshoots by as much;
    # its longest span may then outlast its window). Alone 0 rows of
    # 40 part; beside 24 busy processes on 8 cores 0 to 3 do, never
    # the same ones. Work of the loop's that stands outside every
    # iteration parts every row. So the names, the arithmetic and the
    # sums over the run are held exactly, and a quarter of the rows
    # may part.
    parted = set()
    for i, row in enumerate(rows):
        assert set(row["host_ms"]) <= set(SERVE_PHASES)
        phase, ms = row["host_longest"]
        assert phase in SERVE_PHASES and ms > 0
        if ms > row["window_ms"] * 1.02:
            parted.add(i)
        assert row["host_iter_ms"] == pytest.approx(sum(
            ms for p, ms in row["host_ms"].items()
            if p not in ("fence.device_get", "idle")), abs=2e-3)
    # a fence's own bookkeeping lands on the next row: the sums agree
    # over the run, and row by row once the first is past (less the
    # tens of microseconds between two iterations, which are no phase)
    assert sum(sum(r["host_ms"].values()) for r in rows) == pytest.approx(
        sum(r["window_ms"] for r in rows), rel=0.02)
    for i, row in enumerate(rows[1:], 1):
        if sum(row["host_ms"].values()) != pytest.approx(
                row["window_ms"], rel=0.05, abs=0.25):
            parted.add(i)
    assert len(parted) <= len(rows) // 4, [
        (i, rows[i]["window_ms"], rows[i]["host_ms"]) for i in sorted(parted)]
    assert any("idle" in r["host_ms"] for r in rows)


def test_the_ring_is_bounded_and_outlives_the_engine(model_and_params,
                                                     monkeypatch):
    assert trace_mod._ring.maxlen == trace_mod.RING_SPANS == 65536
    engine = build(model_and_params)
    loop = ServingLoop(engine)
    loop.serve([request("x")])
    loop_id, n = loop._id, len(spans_of(loop))
    assert n > 0
    del engine, loop
    gc.collect()
    kept = [s for s in trace_mod.recent_spans() if s[0] == loop_id]
    assert len(kept) == n
    assert all(len(s) == 6 and s[2] in SERVE_PHASES for s in kept)
    # bounded: the oldest go first
    monkeypatch.setattr(trace_mod, "_ring", collections.deque(maxlen=8))
    trace = StepTrace()
    for i in range(5):
        trace.begin_iteration(99, i)
        for phase in ("admit", "decode.dispatch", "fence.device_get"):
            with trace.span("serve/" + phase):
                pass
        trace.end_iteration(float(i))
    got = trace_mod.recent_spans()
    assert len(got) == 8 and got[-1][1] == 4 and got[0][1] == 2


def test_spans_outside_an_iteration_stay_out_of_the_ring(model_and_params):
    """`fetch_state` from a caller that is not the loop (the
    benchmark's launch at the window's close) is timed and named, and
    is no iteration's."""
    engine = build(model_and_params)
    before = len(trace_mod.recent_spans())
    engine.start_request(1, np.arange(6, dtype=np.int32), max_new=3)
    engine.decode_block(1)
    engine.fetch_state()
    assert len(trace_mod.recent_spans()) == before
    totals = engine.monitor.trace.drain()
    assert set(totals) == {
        "serve/activate", "serve/activate.first_update",
        "serve/activate.other_updates", "serve/fence.device_get",
        "serve/fence.bookkeeping"}
    # totals are self times: the parent's is what its children left
    assert totals["serve/activate"]["ms"] < \
        totals["serve/activate.first_update"]["ms"]


def test_without_a_profiler_api_everything_still_runs(model_and_params,
                                                      monkeypatch):
    monkeypatch.setattr(trace_mod, "_TRACE_ANNOTATION", False)
    engine = build(model_and_params)
    loop = ServingLoop(engine)
    assert len(loop.serve([request("p"), request("q", at=0.5)])) == 2
    assert {s[2] for s in spans_of(loop)} == set(SERVE_PHASES)


def test_a_crash_dump_holds_the_last_iterations_spans(model_and_params,
                                                      tmp_path):
    engine = build(model_and_params, monitor={
        "enabled": True, "sinks": ["jsonl"], "output_path": str(tmp_path)})
    loop = ServingLoop(engine)
    loop.submit(request("doomed", new=30))
    real, calls = engine.fetch_state, []

    def failing():
        calls.append(1)
        if len(calls) == 4:
            raise RuntimeError("fence lost")
        return real()

    engine.fetch_state = failing
    with pytest.raises(RuntimeError):
        loop.run()
    engine.monitor.close()
    (path,) = glob.glob(str(tmp_path / "**" / "flight_*.json"),
                        recursive=True)
    with open(path) as f:
        spans = json.load(f)["extra"]["serve_spans"]
    mine = [s for s in spans if s[0] == loop._id]
    # the failed iteration's closed spans are there, with no fence
    assert mine[-1][1] == loop._iteration and mine[-1][5] is None
    assert mine[-1][2] == "decode.dispatch"
    # and the rows before it say where the host's time went
    with open(path) as f:
        events = json.load(f)["events"]
    assert any("host_longest" in e for e in events
               if e.get("kind") == "decode_batch")


def test_ten_spans_with_nobody_listening_cost_under_100_us():
    """One synthetic iteration: ten phases, no profiler, no sink."""
    trace = StepTrace()
    phases = [p for p in SERVE_PHASES if p != "idle"]
    took = []
    for i in range(200):
        t0 = time.perf_counter()
        trace.begin_iteration(1, i)
        for phase in phases:
            with trace.span("serve/" + phase, slot=3):
                pass
        trace.end_iteration(0.0)
        took.append(time.perf_counter() - t0)
        if i % 4 == 3:
            trace.drain()
    assert len(phases) == 10
    assert statistics.median(took) < 100e-6, statistics.median(took)
