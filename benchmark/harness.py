"""What every cell shares: finding a cell's files by the names in
`BENCHMARK.json`, the look for a chip, the compile cache and its
counters, the traced sub-window, the per-layer readers and the result
line. What belongs to one kind of traffic is in `benchmark/kinds/`.
"""

import importlib
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
TRACE_DIR = os.path.join(REPO, ".bench_trace")


def say(*parts):
    print("[bench]", *parts, flush=True)


class Refused(SystemExit):
    """The run cannot be made here (no chip, unknown cell)."""

    def __init__(self, message):
        print("benchmark: " + message, file=sys.stderr, flush=True)
        super().__init__(2)


# ----------------------------------------------------------------------
# cells
# ----------------------------------------------------------------------
def load_benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench, name):
    """The cell's entry with its configuration and traffic files."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; it has "
                      f"{sorted(cells)}")
    cell = dict(cells[name])
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(REPO, config["file"])) as f:
        cell["sizes"] = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        cell["mix"] = json.load(f)
    if cell["mix"]["chips"] != cell["chips"]:
        raise Refused(f"{name}: BENCHMARK.json says {cell['chips']} chips, "
                      f"the traffic file {cell['mix']['chips']}")
    return cell


def metrics_of(bench, section, cell_name):
    """The metrics of `section` that this cell reports."""
    return [m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]


def merged(base, over):
    """`base` with `over` laid on top, dict by dict."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


# ----------------------------------------------------------------------
# device, compile cache
# ----------------------------------------------------------------------
def device_info():
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def require_tpu(chips):
    info = device_info()
    if info["platform"] != "tpu":
        raise Refused("needs a TPU and falls back to nothing: "
                      f"jax.devices()[0].platform is {info['platform']!r}")
    if info["count"] < chips:
        raise Refused(f"the cell needs {chips} chips, jax finds "
                      f"{info['count']}")
    return info


def memory_peak_bytes():
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def enable_compile_cache():
    """The program's own rule (`JAX_COMPILATION_CACHE_DIR`, else
    `.jax_cache/` in the checkout), and every program stored however
    quickly it compiled, so that a warm run compiles nothing."""
    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class CompileCounter:
    """Counts what jax's own monitoring reports (copied from
    `chip_smoke.CompileCounter`): compile requests that consulted the
    persistent cache, and how many it could not answer."""
    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache":
              "requests",
              "/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __enter__(self):
        import jax
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_event(self, event, **_):
        key = self.EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def snapshot(self):
        c = dict(self.counts)
        c["compiled"] = c["requests"] - c["cache_hits"]
        return c


# ----------------------------------------------------------------------
# the traced sub-window
# ----------------------------------------------------------------------
class TracedWindow:
    """`with TracedWindow(name) as tw:` profiles its body into a fixed
    directory of the checkout under the span `bench/window`. The file
    is read and reduced only when `tw.trace` is first asked for, which
    the kinds do after the window has closed. The caller fences the
    device before and inside the end of the body."""

    def __init__(self, name):
        self.dir = os.path.join(TRACE_DIR, name)
        self.open = False
        self._trace = None

    def __enter__(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation("bench/window")
        self._span.__enter__()
        self.open = True
        return self

    def __exit__(self, *exc):
        import jax
        self._span.__exit__(*exc)
        jax.profiler.stop_trace()
        self.open = False

    @property
    def trace(self):
        from benchmark import trace_reduce
        if self._trace is None and not self.open and \
                os.path.isdir(self.dir):
            self._trace = trace_reduce.load(
                trace_reduce.find_xplane(self.dir))
            shutil.rmtree(self.dir, ignore_errors=True)
        return self._trace


# ----------------------------------------------------------------------
# readers, result line
# ----------------------------------------------------------------------
def read_metric(name, ctx):
    """`benchmark/metrics/<name>.py`'s `read(ctx)`: a number, or None
    where it finds nothing to read."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


CONTROLS = {0: None, 1: "control", 2: "control_program"}


def run_cell(workload, seed, seconds, trace, t_start, control=0,
             need_tpu=True, check_only=False, keep_checks=False):
    """One run of one cell; returns the result object of the last
    line. `control` 1 hands the kind the mix's `control` (the one that
    `correct` is shown to fail on), 2 its `control_program` (a
    lower-precision path of the program's own, switched on and read).
    `need_tpu=False` is for the tests, which drive the rest of a run on
    the CPU at a tiny size."""
    bench = load_benchmark()
    cell = load_cell(bench, workload)
    key = CONTROLS[int(control)]
    if key is not None and key not in cell["mix"]:
        raise Refused(f"{workload}: its traffic file has no {key!r}")
    control = cell["mix"][key] if key else None
    info = require_tpu(cell["chips"]) if need_tpu else device_info()
    say(f"platform={info['platform']} device_kind={info['kind']} "
        f"count={info['count']} workload={workload} seed={seed} "
        f"seconds={seconds} trace={trace} control={key}")
    say("compile cache:", enable_compile_cache())
    kind = importlib.import_module("benchmark.kinds." + cell["mix"]["kind"])
    with CompileCounter() as compiles:
        out = kind.run(cell, seed=seed, seconds=seconds, trace=bool(trace),
                       control=control, t_start=t_start, compiles=compiles,
                       check_only=check_only)
    for c in out["checks"]:
        say(f"check {c['name']}: {c['value']:.6g} (limit {c['limit']:.6g}) "
            f"{'ok' if c['ok'] else 'OVER'}")
    correct = bool(out["checks"]) and all(c["ok"] for c in out["checks"])
    if trace:
        ctx = dict(out["ctx"], cell=cell, device=info,
                   trace=out.get("trace"))
        values = {}
        for m in metrics_of(bench, "per_layer", workload):
            v = read_metric(m["name"], ctx)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {m["name"]: {"value": float(out["end_to_end"][m["name"]]),
                              "unit": m["unit"]}
                  for m in metrics_of(bench, "end_to_end", workload)}
    device = dict(info, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": values, "device": device}
    if keep_checks:
        result["checks"] = out["checks"]
    if trace and out.get("trace") is not None:
        from benchmark import trace_reduce
        device["busy_s"] = trace_reduce.busy_seconds(out["trace"])
        device["window_s"] = trace_reduce.window_seconds(out["trace"])
        result["breakdown"] = trace_reduce.breakdown(out["trace"])
    return result


def main(argv, t_start):
    import argparse
    ap = argparse.ArgumentParser(
        description="one run of one cell of BENCHMARK.json, on a TPU")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=sorted(CONTROLS),
                    default=0,
                    help="1: the cell's lower-precision control, which "
                         "must come out as not correct; 2: the program's "
                         "own lower-precision path, where the mix names one")
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, args.trace,
                      t_start, control=args.control)
    print(json.dumps(result), flush=True)
    return 0
