"""Device time by region of the serving programs, for ANY vocabulary
of regions: the join of `scope_reduce.py` (whose `REGIONS` is closed
to the paged programs' names) and of `state_scopes.py` (closed to the
recurrent ones'), with the list of regions an argument. An event's
program is the launch that contains it, its name stack the program
registry's at its own name; an event the map does not know takes the
name stack of the event that contains it on the line; its time is its
self time (every moment goes to the event that began last, so the
regions' times add up to the busy time); its region is the innermost
component of its name stack that is in the vocabulary.

A program without the regions a reader `needs` (the GPT-2 and Brumby
cells, the parent commit) gives None, which is not 0%.

`PAGED_STATE` is the benchmark's own copy of the vocabulary of a model
that keeps K/V pages and a state-space mixer's state in every layer
(`deepspeed_tpu/utils/scopes.py`, `SCOPES_PAGED_STATE`; a test holds
the two equal).
"""

import bisect

from benchmark import scope_reduce, trace_reduce
from benchmark.harness import say

SSM = ("state_reset", "ssm_conv", "ssm_chunk", "state_update")
PAGED_STATE = ("embed", "layers", "attn_qkv", "kv_write", "kv_gather",
               "attn") + SSM + ("attn_out", "mlp", "head", "sample",
                                "bookkeeping")
ELSEWHERE = "(no region)"


def region_of(name_stack, regions):
    for part in reversed((name_stack or "").split("/")):
        if part in regions:
            return part
    return ELSEWHERE


def region_seconds(trace, regions, needs,
                   scopes_of=scope_reduce.registry_scopes):
    """{region of `regions`: self seconds inside the window}, averaged
    over the devices; None if no program launched in the window names
    any region of `needs`."""
    t0, t1 = trace.window
    maps, total = {}, {}
    for lines in trace.devices.values():
        launches = sorted(trace_reduce.clip(
            lines.get(trace_reduce.MODULES_LINE, []), t0, t1),
            key=lambda x: x[1])
        starts = [s for _, s, _ in launches]
        stack = []                   # [name stack, end, self seconds]

        def close():
            name_stack, _, secs = stack.pop()
            region = region_of(name_stack, regions)
            total[region] = total.get(region, 0.0) + secs

        for text, s, e in sorted(
                trace_reduce.clip(lines[trace_reduce.OPS_LINE], t0, t1),
                key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][1] <= s:
                close()
            i = bisect.bisect_right(starts, s) - 1
            name_stack = None
            if i >= 0 and launches[i][2] > s:
                program = scope_reduce.program_of(launches[i][0])
                if program not in maps:
                    maps[program] = scopes_of(program) or {}
                name_stack = maps[program].get(trace_reduce.own_name(text))
            # [s, e) comes off the events running under it, the
            # innermost first
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
    if not any(region_of(v, regions) in needs for m in maps.values()
               for v in m.values()):
        return None
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in total.items()}


_last = (None, None, None)           # (trace, regions, its region_seconds)


def seconds(ctx, regions, needs, *wanted):
    """Seconds of the traced window spent in the regions `wanted`;
    None without a trace or without the regions `needs`. One reduction
    per trace and vocabulary, its whole split said on a `[bench]`
    line."""
    global _last
    trace = ctx.get("trace")
    if trace is None:
        return None
    if _last[0] is not trace or _last[1] != regions:
        secs = region_seconds(trace, regions, needs)
        _last = (trace, regions, secs)
        if secs is not None:
            window = trace_reduce.window_seconds(trace)
            say("regions: % of the window:", ", ".join(
                f"{k} {100 * v / window:.2f}" for k, v in sorted(
                    secs.items(), key=lambda kv: -kv[1])))
    secs = _last[2]
    if secs is None:
        return None
    return sum(secs.get(r, 0.0) for r in wanted)


def paged_state_seconds(ctx, *wanted):
    """`seconds` of a model that keeps pages and a state-space state:
    None for a program without the state-space regions."""
    return seconds(ctx, PAGED_STATE, SSM, *wanted)


def launches(ctx, pattern):
    """Launches of the programs matching `pattern` that lie wholly
    inside the traced window."""
    return len(trace_reduce.module_durations(ctx["trace"], pattern))
