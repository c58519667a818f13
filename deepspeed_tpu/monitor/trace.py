"""Step tracing: named spans without per-step device fences.

The legacy `wall_clock_breakdown` timers (`utils/timer.py`) call
`jax.effects_barrier()` on every start/stop — per MICRO-step — which
serializes exactly the async-dispatch pipeline the engine is built
around. Spans here do two things instead:

  * when a JAX profiler is attached, each span wraps its region in
    `jax.profiler.TraceAnnotation`, so forward/backward/step/ckpt/
    prefetch show up as named ranges in the trace viewer (the
    annotation is near-free when no profiler is listening);
  * host wall time per span is accumulated WITHOUT any device fence
    and reported fence-aligned at the engine's sync fences. Under
    async dispatch a span therefore measures host-side DISPATCH time
    (what the hot loop actually pays), not device execution — device
    time belongs to the profiler. This is the documented
    `wall_clock_breakdown` behavior change (docs/monitoring.md).

The serving loop times its own iteration the same way: one span a host
phase (`SERVE_PHASES`), on the program's clock and the profiler's. A
closed span goes to the totals (the `decode_batch` fence row drains
them), to the Perfetto exporter, and, inside a serving iteration, to
one bounded process-wide ring (`recent_spans`) that outlives the
engine: a reader that runs after engine and loop are gone (the
benchmark's) splits the device's idle gaps by the phase the host was
in from it.
"""

import collections
import itertools
import threading
import time

SPAN_FORWARD = "forward"
SPAN_BACKWARD = "backward"
SPAN_STEP = "step"
SPAN_CKPT = "ckpt"
SPAN_PREFETCH = "prefetch"

# The host phases of one serving iteration, each the range
# `ds_tpu/serve/<phase>`. They do not overlap except parent and child
# (`activate` holds the two `activate.*`) and together cover
# `ServingLoop.step` from entry to return; `idle` is ONE span from the
# first step that finds nothing to do to the next that does, however
# often the loop polled between them.
SERVE_PHASES = (
    "admit", "prefill.pages", "prefill.dispatch",
    "activate", "activate.first_update", "activate.other_updates",
    "decode.pages", "decode.dispatch",
    "fence.device_get", "fence.bookkeeping", "idle")
SERVE_PREFIX = "serve/"

# (loop id, iteration, phase, t0 on time.perf_counter, duration s, the
# loop's clock at the iteration's fence): about 150 s of a loop of ten
# spans every 24 ms
RING_SPANS = 65536
_ring = collections.deque(maxlen=RING_SPANS)
_loop_ids = itertools.count(1)


def new_loop_id():
    """What the spans of one `ServingLoop` share."""
    return next(_loop_ids)


def recent_spans():
    """The serving iterations' closed spans of this process, oldest
    first, those of loops and engines long deleted among them."""
    return list(_ring)


_TRACE_ANNOTATION = None


def _annotation_cls():
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            import jax
            _TRACE_ANNOTATION = jax.profiler.TraceAnnotation
        except Exception:  # ds-lint: allow[BROADEXC] profiler API varies across jax versions; spans degrade to wall time only
            _TRACE_ANNOTATION = False
    return _TRACE_ANNOTATION


def _annotation(name, args):
    cls = _annotation_cls()
    if not cls:
        return None
    try:
        return cls("ds_tpu/" + name, **args)
    except Exception:  # ds-lint: allow[BROADEXC] profiler annotation is decorative; the hot path must not fail on it
        return None


class _Span:
    __slots__ = ("args", "parent", "inner", "t0", "annotation")

    def __init__(self, name, args, parent, t0=None):
        self.args = args
        self.parent = parent     # the span open when this one began
        self.inner = 0.0         # seconds inside spans it was parent of
        self.t0 = time.perf_counter() if t0 is None else t0
        self.annotation = _annotation(name, args)
        if self.annotation is not None:
            try:
                self.annotation.__enter__()
            except Exception:  # ds-lint: allow[BROADEXC] profiler annotation is decorative; the hot path must not fail on it
                self.annotation = None


class StepTrace:
    """start/stop named spans (timer-style, so the engine's split
    forward()/backward()/step() call sites can use it) plus a `span`
    context manager; totals drain at fences. A span that closes inside
    another gives its time to its own name alone: totals are self
    times (`choosing-metrics` section 4), so the names of one thread
    sum to its wall time."""

    def __init__(self):
        self._open = {}          # name -> _Span, in the order opened
        self._lock = threading.Lock()
        self._totals = {}
        self._counts = {}
        self._longest = {}       # name -> its longest single self time
        self._export = None      # (name, t0, dur, args) -> TraceExporter
        self._iteration = None   # (loop id, number) of a serving iteration
        self._closed = None      # its closed spans, for the ring
        self._seam = None        # where its last outermost span ended

    def set_export_sink(self, fn):
        """Route every closed span to the Perfetto exporter as well
        (monitor/trace_export.py) — spans are timed once, rendered in
        both the fence metrics and the trace file."""
        self._export = fn

    def begin_iteration(self, loop, number):
        """From here to `end_iteration` every span carries the loop's
        id and the iteration's number and is kept for the ring, and an
        outermost span begins where the one before it ended: the few
        microseconds of the loop's own code between two phases count
        to the later one, so the phases cover the iteration."""
        self._iteration = (loop, number)
        self._closed = []
        self._seam = time.perf_counter()

    def end_iteration(self, loop_s):
        """The iteration's spans go to the process-wide ring, stamped
        with the loop's clock at its fence (None: it had none)."""
        if self._iteration is None:
            return
        (loop, number), closed = self._iteration, self._closed
        self._iteration = self._closed = self._seam = None
        _ring.extend((loop, number, name[len(SERVE_PREFIX):], t0, dt, loop_s)
                     for name, t0, dt in closed)

    def start(self, name, **args):
        self._open.pop(name, None)       # one an exception left behind
        if self._iteration is not None:
            args["loop"], args["iteration"] = self._iteration
        parent = next(reversed(self._open.values()), None)
        self._open[name] = _Span(name, args, parent,
                                 self._seam if parent is None else None)

    def stop(self, name):
        sp = self._open.pop(name, None)
        if sp is None:
            return
        if sp.annotation is not None:
            try:
                sp.annotation.__exit__(None, None, None)
            except Exception:  # ds-lint: allow[BROADEXC] profiler annotation is decorative; the hot path must not fail on it
                pass
        end = time.perf_counter()
        dt = end - sp.t0
        own = dt - sp.inner
        if sp.parent is not None:
            sp.parent.inner += dt
        with self._lock:
            self._totals[name] = self._totals.get(name, 0.0) + own
            self._counts[name] = self._counts.get(name, 0) + 1
            if own > self._longest.get(name, 0.0):
                self._longest[name] = own
        if self._closed is not None:
            self._closed.append((name, sp.t0, dt))
            if sp.parent is None:
                self._seam = end
        if self._export is not None:
            try:
                self._export(name, sp.t0, dt, sp.args)
            except Exception:  # ds-lint: allow[BROADEXC] trace-export hook on the hot path; a broken exporter must not stall the step loop
                pass

    def span(self, name, **args):
        return _SpanCtx(self, name, args)

    def drain(self):
        """{name: {"ms": total, "count": n, "ms_per": mean, "max_ms":
        the longest single one}} since the last drain, self times;
        resets the window."""
        with self._lock:
            totals, self._totals = self._totals, {}
            counts, self._counts = self._counts, {}
            longest, self._longest = self._longest, {}
        return {
            name: {"ms": round(totals[name] * 1e3, 3),
                   "count": counts.get(name, 0),
                   "ms_per": round(
                       totals[name] * 1e3 / max(counts.get(name, 1), 1),
                       3),
                   "max_ms": round(longest.get(name, 0.0) * 1e3, 3)}
            for name in totals
        }


class _SpanCtx:
    __slots__ = ("_trace", "_name", "_args")

    def __init__(self, trace, name, args):
        self._trace = trace
        self._name = name
        self._args = args

    def __enter__(self):
        self._trace.start(self._name, **self._args)
        return self

    def __exit__(self, *exc):
        self._trace.stop(self._name)
        return False
