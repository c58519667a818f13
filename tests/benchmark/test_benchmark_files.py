"""`BENCHMARK.json` against its contract, the traffic generator, the
trace reduction and the kernel costs. Nothing here touches a device."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import tiny_copy  # noqa: F401  (puts the repo root on sys.path)
from benchmark import kernel_costs, trace_reduce, traffic

REPO = tiny_copy.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_files(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "traffic", w["traffic"] + ".json"))
    for c in configs.values():
        assert c["file"].startswith("benchmark/")
        assert os.path.exists(os.path.join(REPO, c["file"]))
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(m["bound"] <= 0.1 for m in e2e.values())
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".py")), m["name"]
        # a metric is reported only where the metric it moves is
        moved = e2e[m["moves"]].get("workloads", sorted(cells))
        assert set(m.get("workloads", sorted(cells))) <= set(moved)
    for w in cells:
        for section in ("end_to_end", "per_layer"):
            mine = [m for m in bench[section]
                    if w in m.get("workloads", [w])]
            assert len(mine) >= (2 if section == "end_to_end" else 1)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 4)


def test_configuration_files_state_published_sizes(bench):
    published = {"gpt2-1.5b": (48, 1600, 25), "gpt2-350m": (24, 1024, 16)}
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            sizes = json.load(f)
        assert sizes["source"] == c["source"]
        assert (sizes["n_layer"], sizes["n_embd"], sizes["n_head"]) == \
            published[c["name"]]
        assert sizes["vocab_size"] == 50257 and sizes["n_positions"] == 1024
        assert sizes["reduced"] == c["reduced"] == []


SERVE = {
    "arrivals": {"process": "poisson_conditioned", "rate_per_s": 0.5,
                 "preroll_s": 20, "schedule_seed": 23, "seed_jitter_s": 0.4},
    "prompt_tokens": {"dist": "lognormal", "median": 160, "sigma": 0.8,
                      "min": 16, "max": 768},
    "output_tokens": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                      "min": 16, "max": 240},
    "max_total_tokens": 1024, "tokens": {"dist": "uniform"},
}


@pytest.mark.parametrize("process", ["poisson_conditioned", "jittered_grid",
                                     "gamma"])
def test_serving_traffic_same_work_for_every_seed(process):
    spec = dict(SERVE, arrivals=dict(SERVE["arrivals"], process=process,
                                     cv=2.0))
    runs = [traffic.serve_requests(spec, 50257, 51.0, seed)
            for seed in (1, 2, 2**31 + 99)]
    again = traffic.serve_requests(spec, 50257, 51.0, 1)
    assert all(np.array_equal(a["tokens"], b["tokens"])
               for a, b in zip(runs[0], again))
    # one schedule for every seed; the seed draws the prompts' tokens
    # and moves each arrival by at most the jitter
    by_rid = lambda run: {r["rid"]: r for r in run}
    for run in runs[1:]:
        a, b = by_rid(run), by_rid(runs[0])
        assert a.keys() == b.keys()
        for rid in a:
            assert len(a[rid]["tokens"]) == len(b[rid]["tokens"])
            assert a[rid]["max_new_tokens"] == b[rid]["max_new_tokens"]
            assert abs(a[rid]["arrival_s"] - b[rid]["arrival_s"]) <= 0.8
        assert any(a[rid]["arrival_s"] != b[rid]["arrival_s"] for rid in a)
        assert not all(np.array_equal(a[rid]["tokens"], b[rid]["tokens"])
                       for rid in a)
    other = traffic.serve_requests(
        dict(spec, arrivals=dict(spec["arrivals"], schedule_seed=24)),
        50257, 51.0, 1)

    def work(run, lo, hi):
        rs = [r for r in run if lo <= r["arrival_s"] < hi]
        return (sorted(len(r["tokens"]) for r in rs),
                sorted(r["max_new_tokens"] for r in rs))

    first = work(runs[0], 0.0, 51.0)
    assert len(first[0]) == 26                  # round(0.5 * 51)
    for run in runs:
        # the window: the same count and multiset for every seed
        assert work(run, 0.0, 51.0) == first
        # the pre-roll repeats the window's last 20 s (by the schedule,
        # before the jitter), one window earlier
        assert [r["arrival_s"] for r in run] == \
            sorted(r["arrival_s"] for r in run)
        mine = by_rid(run)
        early = [r for r in run if r["rid"].startswith("p")]
        assert early and all(r["arrival_s"] < 0.0 for r in early)
        for r in early:
            twin = mine["w" + r["rid"].split(".")[1]]
            assert twin["arrival_s"] == pytest.approx(r["arrival_s"] + 51.0)
            assert twin["arrival_s"] >= 31.0 - 0.4
            assert (len(twin["tokens"]), twin["max_new_tokens"]) == \
                (len(r["tokens"]), r["max_new_tokens"])
    assert [r["arrival_s"] for r in runs[0]] != \
        [r["arrival_s"] for r in other]
    assert work(other, 0.0, 51.0) == first
    for r in runs[0]:
        assert 16 <= len(r["tokens"]) <= 768
        assert len(r["tokens"]) + r["max_new_tokens"] <= 1024
    # a traced run's tail: the schedule goes on after the window, and
    # the window's own requests are the same with and without it
    tailed = traffic.serve_requests(spec, 50257, 51.0, 1, tail_s=20.0)
    body = [r for r in tailed if not r["rid"].startswith("t")]
    assert len(body) == len(runs[0]) and all(
        a["rid"] == b["rid"] and a["arrival_s"] == b["arrival_s"] and
        np.array_equal(a["tokens"], b["tokens"])
        for a, b in zip(body, runs[0]))
    tail = [r for r in tailed if r["rid"].startswith("t")]
    assert tail and all(51.0 <= r["arrival_s"] < 71.4 for r in tail)
    twins = by_rid(tailed)
    for r in tail:
        twin = twins["w" + r["rid"].split(".")[1]]
        assert r["arrival_s"] == pytest.approx(twin["arrival_s"] + 51.0)
        assert r["max_new_tokens"] == twin["max_new_tokens"]
    # a window shorter than the pre-roll wraps round more than once
    short = traffic.serve_requests(spec, 50257, 8.0, 1)
    assert min(r["arrival_s"] for r in short) >= -20.4
    periods = [r["rid"].split(".")[0] for r in short]
    assert periods.count("p1") == periods.count("p2") == 4     # 0.5/s x 8 s
    assert 0 < periods.count("p3") <= 4


@pytest.mark.parametrize("spec,want", [
    ({"dist": "uniform", "min": 512, "max": 960}, [568, 680, 792, 904]),
    ({"dist": "lognormal", "median": 160, "sigma": 0.8, "min": 16,
      "max": 200}, [64, 124, 200, 200])])
def test_lengths_are_the_quantiles_of_their_distribution(spec, want):
    assert traffic.quantile_lengths(spec, 4).tolist() == want
    with pytest.raises(ValueError):
        traffic.quantile_lengths(dict(spec, dist="fixed"), 4)


def test_training_traffic_is_seeded_and_rows_differ():
    spec = {"tokens": {"dist": "zipf", "exponent": 1.0}}
    a = next(traffic.train_batches(spec, 50257, 1, 10, 1024, 5))
    b = next(traffic.train_batches(spec, 50257, 1, 10, 1024, 5))
    c = next(traffic.train_batches(spec, 50257, 1, 10, 1024, 6))
    assert np.array_equal(a["input_ids"], b["input_ids"])
    assert not np.array_equal(a["input_ids"], c["input_ids"])
    rows = a["input_ids"][0]
    assert rows.dtype == np.int32 and rows.min() >= 0 and rows.max() < 50257
    assert len({r.tobytes() for r in rows}) == 10
    # Zipf: the commonest token takes far more than a uniform share
    assert np.bincount(rows.ravel()).max() > 50 * rows.size / 50257


# a hand-made trace with known answers: two devices, a `while` that
# spans two of its body's operations, two operations that overlap
PLANES = {
    "/device:TPU:0": {
        "XLA Ops": [("%while.1 = (f32[]) while(%t), body=%b", 1.0, 3.0),
                    ("%fusion.7 = f32[] fusion(%p), kind=kLoop", 1.0, 2.0),
                    ("%copy.3 = f32[] copy(%fusion.7)", 2.0, 2.5),
                    ("%all-reduce.2 = f32[] all-reduce(%copy.3)", 4.0, 5.0),
                    ("%fusion.9 = f32[] fusion(%fusion.7), kind=kLoop",
                     4.5, 6.0)],
        "XLA Modules": [("jit_decode_fn(1)", 1.0, 3.0),
                        ("jit_prefill_fn(2)", 4.0, 6.0)]},
    "/device:TPU:1": {"XLA Ops": [
        ("%fusion.7 = f32[] fusion(%p), kind=kLoop", 1.0, 2.0)]},
    "/host:CPU": {"main": [("bench/window", 0.0, 10.0),
                           ("bench/step", 0.5, 3.5),
                           ("bench/fence", 3.2, 3.9),
                           ("other", 0.0, 10.0)]},
}


def test_trace_reduce_known_answers():
    tr = trace_reduce.from_planes(PLANES)
    assert tr.window == (0.0, 10.0)
    # device 0 is busy over [1,3] and [4,6]; device 1 over [1,2]
    assert trace_reduce.busy_seconds(tr) == pytest.approx((4.0 + 1.0) / 2)
    ops = trace_reduce.op_seconds(tr)
    assert ops["fusion(kLoop)"] == pytest.approx((1.0 + 1.5 + 1.0) / 2)
    assert ops["while"] == pytest.approx(0.5 / 2)      # self time
    assert ops["copy"] == pytest.approx(0.5 / 2)
    assert trace_reduce.module_durations(tr, "decode") == [2.0]
    gaps = trace_reduce.idle_gaps(tr)
    # [0,1] before any span, [3,4] began inside bench/step, [6,10] after
    assert gaps == {"(no span)": pytest.approx(5.0),
                    "bench/step": pytest.approx(1.0)}
    secs, count = trace_reduce.matching_seconds(tr, r"^fusion\.9")
    assert (secs, count) == (pytest.approx(0.75), 1)
    b = trace_reduce.breakdown(tr)
    assert b["device_ops"][0][0] == "fusion(kLoop)" and len(b["idle_gaps"]) == 2


def test_trace_reduce_on_a_trace_recorded_on_the_chip():
    """`tiny_trace.json`: three launches of one small jitted program on
    a TPU v5e, 2 ms of host sleep after each (my chip run, PR 23)."""
    with open(os.path.join(os.path.dirname(__file__),
                           "tiny_trace.json")) as f:
        planes = {p: {l: [tuple(s) for s in spans]
                      for l, spans in lines.items()}
                  for p, lines in json.load(f).items()}
    tr = trace_reduce.from_planes(planes)
    assert len(tr.devices) == 1
    window = trace_reduce.window_seconds(tr)
    busy = trace_reduce.busy_seconds(tr)
    assert 0.006 < window < 0.5 and 0.0 < busy < window - 0.006
    assert len(trace_reduce.module_durations(tr, "jit")) == 3
    assert sum(trace_reduce.op_seconds(tr).values()) == pytest.approx(
        busy, rel=0.05)
    assert trace_reduce.idle_gaps(tr)


def test_kernel_costs_and_peaks():
    sizes = {"n_layer": 48, "n_embd": 1600, "n_head": 25,
             "vocab_size": 50257, "n_positions": 1024}
    assert kernel_costs.param_count(sizes) == 1557611200
    cost = kernel_costs.flash_causal_cost(10, 25, 1024, 64)
    # causal: half of 2 (fwd) and 5 (bwd) full [T, T, d] matmuls
    full = 2 * 10 * 25 * 1024 * 1024 * 64
    assert cost["fwd"][0] == full and cost["bwd"][0] == 5 * full // 2
    peaks = kernel_costs.peaks_for("TPU v5 lite")
    seconds, bound = kernel_costs.roofline_seconds(*cost["fwd"], peaks)
    assert bound == "compute" and seconds == pytest.approx(full / 197e12)
    with pytest.raises(KeyError):
        kernel_costs.peaks_for("TPU v9")


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "gpt2-1.5b.train-zero2", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not out.stdout.strip().endswith("}")
