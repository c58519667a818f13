"""From the profiler's `.xplane.pb` to numbers: device busy and idle
time, time per named operation, idle gaps by what the host was doing.

Read with `jax.profiler.ProfileData` alone. Device planes are
`/device:TPU:<n>`; their line "XLA Ops" holds one event per executed
HLO operation (a `while` spans its body's events, so times per
operation are self times) and "XLA Modules" one per program launch.
Host spans are the `jax.profiler.TraceAnnotation`s whose names start
with `bench/`, put by the benchmark's own files around its calls into
the program; `bench/window` bounds what is counted.
"""

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

Span = Tuple[str, float, float]          # name, start s, end s

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Dict[str, List[Span]]]   # plane -> line -> spans
    host: List[Span]                             # bench/* annotations
    window: Tuple[float, float]


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path, window_name="bench/window"):
    from jax.profiler import ProfileData
    return from_planes(_planes(ProfileData.from_file(path)), window_name)


def _planes(data):
    """ProfileData -> {plane: {line: [(name, start_s, end_s)]}}."""
    out = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            spans = lines.setdefault(line.name, [])
            for ev in line.events:
                s = ev.start_ns * 1e-9
                spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def from_planes(planes, window_name="bench/window"):
    devices = {name: lines for name, lines in planes.items()
               if name.startswith("/device:TPU") and OPS_LINE in lines}
    host = sorted((s for name, lines in planes.items()
                   if name.startswith("/host:")
                   for spans in lines.values() for s in spans
                   if s[0].startswith("bench/")), key=lambda s: s[1])
    marks = [s for s in host if s[0] == window_name]
    if marks:
        window = (min(s[1] for s in marks), max(s[2] for s in marks))
    else:
        ops = [s for d in devices.values() for s in d[OPS_LINE]]
        window = (min(s[1] for s in ops), max(s[2] for s in ops)) \
            if ops else (0.0, 0.0)
    return Trace(devices, [s for s in host if s[0] != window_name], window)


def clip(spans, t0, t1):
    return [(n, max(s, t0), min(e, t1)) for n, s, e in spans
            if e > t0 and s < t1]


def union_seconds(spans):
    total, end = 0.0, float("-inf")
    for _, s, e in sorted(spans, key=lambda x: x[1]):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def busy_seconds(trace):
    """Seconds in which an operation ran, averaged over the devices."""
    t0, t1 = trace.window
    per = [union_seconds(clip(d[OPS_LINE], t0, t1))
           for d in trace.devices.values()]
    return sum(per) / len(per) if per else 0.0


def window_seconds(trace):
    return trace.window[1] - trace.window[0]


def own_name(text):
    """An event of "XLA Ops" is named by its whole HLO instruction,
    operands and all: `%copy.4 = bf16[..] copy(%fusion.2)` -> `copy.4`."""
    return text.split(" = ")[0].lstrip("%")


def base_name(text):
    """`%fusion.123 = .. fusion(..), kind=kLoop` -> `fusion(kLoop)`;
    `%flash_fwd_packed.7 = ..` -> `flash_fwd_packed`."""
    name = own_name(text)
    base = re.sub(r"[.\d]+$", "", name) or name
    kind = re.search(r"\bkind=(k\w+)", text)
    return f"{base}({kind.group(1)})" if kind else base


def self_times(spans):
    """[(name, self seconds)] of nested spans on one line: a span's
    time less that of the spans it contains."""
    out, stack = [], []          # stack of [name, end, self]
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((n, t) for n, _, t in stack)
    return out


def op_seconds(trace, key=base_name):
    """{operation: self seconds}, averaged over the devices."""
    t0, t1 = trace.window
    total = {}
    for d in trace.devices.values():
        for name, t in self_times(clip(d[OPS_LINE], t0, t1)):
            total[key(name)] = total.get(key(name), 0.0) + t
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in total.items()}


def matching_seconds(trace, pattern):
    """Self seconds of operations whose own name matches `pattern`,
    averaged over the devices, and their count on the first device."""
    rx = re.compile(pattern)
    secs = {k: v for k, v in op_seconds(trace, key=own_name).items()
            if rx.search(k)}
    t0, t1 = trace.window
    first = next(iter(trace.devices.values()), {OPS_LINE: []})
    count = sum(1 for n, _, _ in clip(first[OPS_LINE], t0, t1)
                if rx.search(own_name(n)))
    return sum(secs.values()), count


def module_durations(trace, pattern):
    """Durations (s) of the program launches on the first device whose
    name matches `pattern` and that lie wholly inside the window."""
    rx = re.compile(pattern)
    t0, t1 = trace.window
    first = next(iter(trace.devices.values()), {})
    return [e - s for n, s, e in first.get(MODULES_LINE, [])
            if rx.search(n) and s >= t0 and e <= t1]


def idle_gaps(trace, least=1e-4):
    """{host span name: idle seconds}: every gap of the first device
    longer than `least`, given to the `bench/*` span the host was in
    when the gap began ("(no span)" outside all)."""
    t0, t1 = trace.window
    first = next(iter(trace.devices.values()), None)
    if first is None:
        return {}
    gaps, end = [], t0
    for _, s, e in sorted(clip(first[OPS_LINE], t0, t1),
                          key=lambda x: x[1]):
        if s - end > least:
            gaps.append((end, s))
        end = max(end, e)
    if t1 - end > least:
        gaps.append((end, t1))
    out = {}
    for s, e in gaps:
        inside = [h for h in trace.host if h[1] <= s < h[2]]
        # the innermost span: the one that began last
        name = max(inside, key=lambda h: h[1])[0] if inside \
            else "(no span)"
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def breakdown(trace, top=10):
    ops = sorted(op_seconds(trace).items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
