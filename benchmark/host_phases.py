"""The host's phases of a serving iteration, and the device's idle gaps
split by the phase the host was in.

The program's serving loop times its own iteration: one span a host
phase (`deepspeed_tpu/monitor/trace.py`, `SERVE_PHASES`), kept in one
bounded ring of the process that outlives engine and loop
(`recent_spans()`: (loop id, iteration, phase, t0 on
`time.perf_counter`, duration s, the loop's clock at the iteration's
fence)). The kinds delete engine, loop and recorder before a reader
runs and hand readers neither the profile nor a fence row; the ring
and `ctx["trace"]` are what is read here:

  * the window (host clock): the iterations of the run's loop whose
    fence lies in the window, the first `ctx["fences_in_window"]` at a
    loop's clock of 0 or more; ms an iteration by phase, self times (a
    span's duration less its children's);
  * the clock: the profile's clock starts with the profiler, the
    ring's is the process's. `ctx["trace"].host` holds the traced
    tail's K `bench/fence` spans on the profile's clock and each
    encloses exactly one `fence.device_get` of the ring's last K:
    `align` takes the offset from them and says by how much a mapped
    `fence.device_get` leaves its `bench/fence` at worst;
  * the split: every idle interval of the first device (the gaps of
    `trace_reduce.idle_gaps`: "XLA Ops", longer than 1e-4 s, clipped
    to the window) is divided among the innermost program phases it
    overlaps, microsecond for microsecond, `(no phase)` for what lies
    in none, and not given whole to where it began.

A program from before the ring (the parent commit) has no
`recent_spans`: the readers then return None, which is not 0.
"""

import statistics

from benchmark import trace_reduce
from benchmark.harness import say

# the benchmark's own copy of the program's vocabulary: what a metric is
# computed from is part of the yardstick (a test holds the two equal)
PHASES = ("admit", "prefill.pages", "prefill.dispatch",
          "activate", "activate.first_update", "activate.other_updates",
          "decode.pages", "decode.dispatch",
          "fence.device_get", "fence.bookkeeping", "idle")
NO_PHASE = "(no phase)"
# what a loop with the next block in flight would hide, in three parts
READBACK = ("fence.device_get",)
BOOKKEEPING = ("fence.bookkeeping", "admit", "prefill.pages",
               "decode.pages", NO_PHASE)
DISPATCH = ("prefill.dispatch", "activate", "activate.first_update",
            "activate.other_updates", "decode.dispatch")
# the host's own part of an iteration: not the wait inside the
# `device_get`, not the wait for arrivals
WAITS = ("fence.device_get", "idle")
# a phase that holds others: its span is theirs and a little more
PARENTS = tuple(p for p in PHASES
                if any(q.startswith(p + ".") for q in PHASES))
ALIGN_MISS_S = 2e-4
LEAST_GAP_S = 1e-4


def ring():
    """The process's closed serving spans, oldest first; None where the
    program keeps none."""
    from deepspeed_tpu.monitor import trace
    recent = getattr(trace, "recent_spans", None)
    return None if recent is None else recent()


def of_last_loop(spans):
    """The spans of the loop that ran last: a process may have run
    others before it (the tests do)."""
    return [s for s in spans if s[0] == spans[-1][0]] if spans else []


def segments(spans, offset=0.0):
    """[(phase, start, end)] in which the phase was the innermost one
    open, from `(.., phase, t0, duration, ..)` ring entries: a parent's
    span less its children's, in order of time."""
    out, stack = [], []                  # stack of [phase, end, cursor]

    def close(until):
        while stack and stack[-1][1] <= until:
            phase, end, cursor = stack.pop()
            if end > cursor:
                out.append((phase, cursor, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for _, _, phase, t0, dt, _ in sorted(spans, key=lambda s: (s[3], -s[4])):
        s, e = t0 + offset, t0 + dt + offset
        close(s)
        if stack:
            if s > stack[-1][2]:
                out.append((stack[-1][0], stack[-1][2], s))
            stack[-1][2] = max(stack[-1][2], s)
            e = min(e, stack[-1][1])
        stack.append([phase, e, s])
    close(float("inf"))
    return sorted(out, key=lambda x: x[1])


def self_ms(spans):
    """{phase: self ms} of ring entries."""
    total = {}
    for phase, s, e in segments(spans):
        total[phase] = total.get(phase, 0.0) + 1e3 * (e - s)
    return total


def window(spans, fences):
    """The timed window on the host's clock: {"iterations": n,
    "ms": {phase: self ms an iteration}, "longest": (phase, ms, loop_s)
    of the longest single innermost span but `idle`} over the first
    `fences` iterations whose fence reads 0 or more."""
    spans = of_last_loop(spans)
    order = list(dict.fromkeys(
        s[1] for s in spans if s[5] is not None and s[5] >= 0.0))
    inside = set(order[:int(fences or 0)])
    mine = [s for s in spans if s[1] in inside]
    n = len(inside)
    # a wait for arrivals is long by design: the longest innermost span
    # that is the host's doing names the phase a frozen loop stood in
    longest = max((s for s in mine if s[2] not in PARENTS + ("idle",)),
                  key=lambda s: s[4], default=None)
    return {"iterations": n,
            "ms": {p: ms / max(n, 1) for p, ms in self_ms(mine).items()},
            "longest": None if longest is None else
            (longest[2], 1e3 * longest[4], longest[5])}


def host_iter_ms(win):
    return sum(ms for p, ms in win["ms"].items() if p not in WAITS)


def align(trace, spans):
    """(offset s, worst miss s, K): the ring's clock + offset is the
    profile's. The tail's K `bench/fence` spans each enclose one
    `fence.device_get` of the ring's last K; the offset is the median
    of the K differences of their starts, the miss the farthest a
    mapped `fence.device_get` then leaves its `bench/fence`. (None,
    None, 0) where the tail holds no fence."""
    fences = [h for h in trace.host if h[0] == "bench/fence"]
    gets = [s for s in of_last_loop(spans) if s[2] == "fence.device_get"]
    k = min(len(fences), len(gets))
    if not k:
        return None, None, 0
    pairs = list(zip(fences[-k:], gets[-k:]))
    offset = statistics.median(f[1] - g[3] for f, g in pairs)
    miss = max(max(f[1] - (g[3] + offset), (g[3] + g[4] + offset) - f[2],
                   0.0) for f, g in pairs)
    return offset, miss, k


def device_gaps(trace, least=LEAST_GAP_S):
    """[(start, end)] in which no operation ran on the first device,
    longer than `least`, inside the traced window: the gaps that
    `trace_reduce.idle_gaps` gives to the span in which each began."""
    t0, t1 = trace.window
    first = next(iter(trace.devices.values()), None)
    if first is None:
        return []
    gaps, end = [], t0
    for _, s, e in sorted(trace_reduce.clip(first[trace_reduce.OPS_LINE],
                                            t0, t1), key=lambda x: x[1]):
        if s - end > least:
            gaps.append((end, s))
        end = max(end, e)
    if t1 - end > least:
        gaps.append((end, t1))
    return gaps


def split(gaps, segs):
    """{phase: idle seconds}: every gap divided among the segments it
    overlaps, `NO_PHASE` for what lies in none."""
    out = {}
    for g0, g1 in gaps:
        covered = 0.0
        for phase, s, e in segs:
            if e <= g0:
                continue
            if s >= g1:
                break
            part = min(e, g1) - max(s, g0)
            out[phase] = out.get(phase, 0.0) + part
            covered += part
        if g1 - g0 > covered:
            out[NO_PHASE] = out.get(NO_PHASE, 0.0) + (g1 - g0) - covered
    return out


def tail(trace, spans):
    """The traced tail: {"iterations": K, "exposed_s": {phase: device-
    idle seconds while the host was in it}, "miss_s": the alignment's
    worst}. With no fence in the tail: no iterations and nothing
    exposed; with no device plane (the CPU): K and nothing exposed."""
    offset, miss, k = align(trace, spans)
    if not k:
        return {"iterations": 0, "exposed_s": {}, "miss_s": None}
    t0, t1 = trace.window
    near = [s for s in of_last_loop(spans)
            if s[3] + s[4] + offset > t0 and s[3] + offset < t1]
    segs = trace_reduce.clip(segments(near, offset), t0, t1)
    return {"iterations": k, "exposed_s": split(device_gaps(trace), segs),
            "miss_s": miss}


def exposed_ms(tl, phases=None):
    """Device-idle ms an iteration of the tail while the host was in
    one of `phases` (default: any but `idle`)."""
    if not tl["iterations"]:
        return 0.0
    return 1e3 * sum(
        sec for p, sec in tl["exposed_s"].items()
        if (p != "idle" if phases is None else p in phases)
    ) / tl["iterations"]


_last = (None, None)                 # (the trace it was made from, result)


def of_run(ctx):
    """{"window": .., "tail": .., "ring": how many spans of the run
    the ring holds} of the run in this process, made once a traced run
    and printed as the `[bench] host phases:` line; None where the
    program keeps no ring."""
    global _last
    spans = ring()
    if spans is None:
        return None
    if _last[0] is ctx["trace"] and _last[0] is not None:
        return _last[1]
    win = window(spans, ctx.get("fences_in_window"))
    tl = tail(ctx["trace"], spans) if ctx["trace"] is not None else \
        {"iterations": 0, "exposed_s": {}, "miss_s": None}
    out = {"window": win, "tail": tl,
           "ring": len(of_last_loop(spans))}
    _last = (ctx["trace"], out)
    say("host phases:", line(out))
    return out


def line(out):
    win, tl = out["window"], out["tail"]
    phases = [p for p in PHASES + (NO_PHASE,)
              if p in win["ms"] or p in tl["exposed_s"]]
    per = max(tl["iterations"], 1)
    by_phase = ", ".join(
        f"{p} {win['ms'].get(p, 0.0):.3f} | "
        f"{1e3 * tl['exposed_s'].get(p, 0.0) / per:.3f}" for p in phases)
    longest = "none" if win["longest"] is None else \
        "{} {:.3f} ms at loop_s {:.3f}".format(*win["longest"])
    miss = tl["miss_s"]
    clock = "no fence in the tail" if miss is None else \
        f"alignment's worst miss {1e3 * miss:.4f} ms over " \
        f"{tl['iterations']} fences" + (
            " (OVER 0.2 ms: the split is off by as much)"
            if miss > ALIGN_MISS_S else "")
    return (f"ms an iteration over the window's {win['iterations']} | "
            f"device-idle ms an iteration over the tail's "
            f"{tl['iterations']}: {by_phase}; host_iter_ms "
            f"{host_iter_ms(win):.3f}, host_exposed_ms "
            f"{exposed_ms(tl):.3f} = readback "
            f"{exposed_ms(tl, READBACK):.3f} + bookkeeping "
            f"{exposed_ms(tl, BOOKKEEPING):.3f} + dispatch "
            f"{exposed_ms(tl, DISPATCH):.3f}, under idle "
            f"{1e3 * tl['exposed_s'].get('idle', 0.0):.3f} ms in all; "
            f"longest span of the window {longest}; {clock}; "
            f"{out.get('ring', 0)} spans of the run in the ring")
