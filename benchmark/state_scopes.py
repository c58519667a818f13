"""Device time by region of the serving programs of a model whose
cache is recurrent state (`deepspeed_tpu/utils/scopes.py`,
`SCOPES_RECURRENT`): the vocabulary of `scope_reduce.py` is closed, so
the readers of the retention metrics bring their own list and their
own join. It is the same join: an event's program is the launch that
contains it, its name stack the program registry's at its own name, an
event the map does not know takes the name stack of the event that
contains it, its time is its self time, and its region the innermost
component of the name stack that is in `REGIONS`.

A program without the state regions (the GPT-2 cells, the parent
commit) gives None, which is not 0%.
"""

import bisect

from benchmark import scope_reduce, trace_reduce
from benchmark.harness import say

# the benchmark's own copy of the program's vocabulary (a test holds
# the two equal)
STATE = ("state_reset", "retention_chunk", "state_update")
IN_LAYER = ("attn_qkv",) + STATE + ("attn_out", "mlp")
REGIONS = ("embed", "layers") + IN_LAYER + ("head", "sample", "bookkeeping")
ELSEWHERE = "(no region)"


def region_of(name_stack):
    for part in reversed((name_stack or "").split("/")):
        if part in REGIONS:
            return part
    return ELSEWHERE


def region_seconds(trace, scopes_of=scope_reduce.registry_scopes):
    """{region: self seconds inside the window}, averaged over the
    devices; None if no program launched in the window names a state
    region."""
    t0, t1 = trace.window
    maps, total = {}, {}
    for lines in trace.devices.values():
        launches = sorted(trace_reduce.clip(
            lines.get(trace_reduce.MODULES_LINE, []), t0, t1),
            key=lambda x: x[1])
        starts = [s for _, s, _ in launches]
        stack = []                   # [name stack, end, self seconds]
        done = []
        for text, s, e in sorted(
                trace_reduce.clip(lines[trace_reduce.OPS_LINE], t0, t1),
                key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][1] <= s:
                done.append(stack.pop())
            i = bisect.bisect_right(starts, s) - 1
            name_stack = None
            if i >= 0 and launches[i][2] > s:
                program = scope_reduce.program_of(launches[i][0])
                if program not in maps:
                    maps[program] = scopes_of(program) or {}
                name_stack = maps[program].get(trace_reduce.own_name(text))
            if stack:
                stack[-1][2] -= min(e, stack[-1][1]) - s
                if name_stack is None:
                    name_stack = stack[-1][0]
            stack.append([name_stack, e, e - s])
        for name_stack, _, secs in done + stack:
            region = region_of(name_stack)
            total[region] = total.get(region, 0.0) + secs
    if not any(region_of(v) in STATE for m in maps.values()
               for v in m.values()):
        return None
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in total.items()}


_last = (None, None)                 # (trace, its region_seconds)


def seconds(ctx, *regions):
    """Seconds of the traced window spent in `regions`; None without a
    trace or without the state regions. One reduction per trace, its
    whole split said on a `[bench]` line."""
    global _last
    trace = ctx.get("trace")
    if trace is None:
        return None
    if _last[0] is not trace:
        secs = region_seconds(trace)
        _last = (trace, secs)
        if secs is not None:
            window = trace_reduce.window_seconds(trace)
            say("state scopes: % of the window:", ", ".join(
                f"{k} {100 * v / window:.2f}" for k, v in sorted(
                    secs.items(), key=lambda kv: -kv[1])))
    secs = _last[1]
    if secs is None:
        return None
    return sum(secs.get(r, 0.0) for r in regions)


def launches(ctx, pattern):
    """Launches of the programs matching `pattern` that lie wholly
    inside the traced window."""
    return len(trace_reduce.module_durations(ctx["trace"], pattern))
