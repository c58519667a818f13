"""Device time by region of the serving programs.

The program names its regions with `jax.named_scope`
(`deepspeed_tpu/inference/engine.py`, `SCOPE_*`) and leaves its
executables in a registry (`deepspeed_tpu/monitor/programs.py`) that
gives, per program, {HLO instruction name: JAX name stack}. A device
profile names each event of "XLA Ops" by its instruction (`%copy.27 =
..`) and each launch on "XLA Modules" by its program
(`jit_decode_fn(<hash>)`). Here the two are joined:

  * an event's program is the launch that contains it;
  * its name stack is the program's map at its own name; an event the
    map does not know (the compiler inserted it, from no named
    operand) takes the name stack of the event that contains it on the
    line, as a `while` contains its body's; outside every known event
    it is unscoped;
  * its time is its self time: every moment goes to the event that
    began last (a `while` less its body), so the regions' times add
    up to the time in which anything ran;
  * its region is the innermost component of the name stack that is in
    `REGIONS`. Time in `layers` outside every inner region is the layer
    scan itself: each layer's K/V page pool sliced out of the stacked
    pools and written back.

A program from before the scopes (the parent commit, or an executable
that a compilation cache keyed without names kept from it) has a map
without the vocabulary: the reduction then returns None, which is not
0%.
"""

import bisect
import functools
import time

from benchmark import trace_reduce
from benchmark.harness import say

# the benchmark's own copy of the program's vocabulary: what a metric is
# computed from is part of the yardstick (a test holds the two equal)
IN_LAYER = ("attn_qkv", "kv_write", "kv_gather", "attn", "attn_out", "mlp")
REGIONS = ("embed", "layers") + IN_LAYER + ("head", "sample", "bookkeeping")
NAMED_ELSEWHERE = "(named, no region)"
UNSCOPED = "(unscoped)"


def program_of(launch):
    """`jit_decode_fn(16694157788279218512)` -> `jit_decode_fn`."""
    return launch.split("(")[0]


@functools.lru_cache(maxsize=None)     # a few thousand name stacks
def region_of(name_stack):
    if name_stack is None:
        return UNSCOPED
    for part in reversed(name_stack.split("/")):
        if part in REGIONS:
            return part
    return NAMED_ELSEWHERE


def registry_scopes(program):
    """The program registry's map for `program`; None where the
    program (the parent commit) has no registry or no such program."""
    try:
        from deepspeed_tpu.monitor import programs
    except ImportError:
        return None
    t0 = time.perf_counter()
    scopes = programs.op_scopes(program)
    if scopes is not None:
        say(f"scopes: {program}: {len(scopes)} named instructions, read "
            f"in {time.perf_counter() - t0:.3f} s (as_text + parse the "
            "first time)")
    return scopes


def region_seconds(trace, scopes_of=registry_scopes):
    """{region: self seconds inside the window}, averaged over the
    devices, with `UNSCOPED` and `NAMED_ELSEWHERE` beside the regions;
    the values sum to the busy seconds. None if no program launched in
    the window has any of the vocabulary in its map."""
    t0, t1 = trace.window
    maps, total = {}, {}

    def scopes(program):
        if program not in maps:
            maps[program] = scopes_of(program) or {}
        return maps[program]

    for lines in trace.devices.values():
        launches = sorted(trace_reduce.clip(
            lines.get(trace_reduce.MODULES_LINE, []), t0, t1),
            key=lambda x: x[1])
        starts = [s for _, s, _ in launches]
        stack = []                   # [name stack, end, self seconds]

        def close():
            stackname, _, secs = stack.pop()
            region = region_of(stackname)
            total[region] = total.get(region, 0.0) + secs

        for text, s, e in sorted(
                trace_reduce.clip(lines[trace_reduce.OPS_LINE], t0, t1),
                key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][1] <= s:
                close()
            i = bisect.bisect_right(starts, s) - 1
            name_stack = None
            if i >= 0 and launches[i][2] > s:
                name_stack = scopes(program_of(launches[i][0])).get(
                    trace_reduce.own_name(text))
            # every moment belongs to the event that began last: [s, e)
            # comes off the events running under it, the innermost
            # first (one that merely overlaps its neighbour's end takes
            # the rest from the event below)
            lo = s
            for frame in reversed(stack):
                if name_stack is None and frame[1] >= e:
                    name_stack = frame[0]        # the one that contains it
                hi = min(e, frame[1])
                if hi > lo:
                    frame[2] -= hi - lo
                    lo = hi
            stack.append([name_stack, e, e - s])
        while stack:
            close()
    if not any(region_of(v) in REGIONS for m in maps.values()
               for v in m.values()):
        say("scopes: none of the vocabulary in the maps of",
            sorted(maps) or "no program", "(no registry, or executables "
            "from before the scopes): no region metric")
        return None
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in total.items()}


_last = (None, None)                 # (trace, its region_seconds)


def share(ctx, *regions):
    """% of the traced window spent in `regions`; None without a trace
    or without the vocabulary. The reduction is made once per trace
    and says its whole split on a `[bench]` line."""
    global _last
    trace = ctx["trace"]
    if trace is None:
        return None
    if _last[0] is not trace:
        t0 = time.perf_counter()
        secs = region_seconds(trace)
        _last = (trace, secs)
        if secs is not None:
            window = trace_reduce.window_seconds(trace)
            say(f"scopes: maps read and events joined in "
                f"{time.perf_counter() - t0:.2f} s; % of the window:",
                ", ".join(
                f"{k} {100 * v / window:.2f}" for k, v in sorted(
                    secs.items(), key=lambda kv: -kv[1])),
                f"; sum {100 * sum(secs.values()) / window:.2f}, busy "
                f"{100 * trace_reduce.busy_seconds(trace) / window:.2f}")
    secs = _last[1]
    if secs is None:
        return None
    return 100.0 * sum(secs.get(r, 0.0) for r in regions) / \
        trace_reduce.window_seconds(trace)
