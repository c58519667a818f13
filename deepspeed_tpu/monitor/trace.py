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
"""

import contextlib
import threading
import time

SPAN_FORWARD = "forward"
SPAN_BACKWARD = "backward"
SPAN_STEP = "step"
SPAN_CKPT = "ckpt"
SPAN_PREFETCH = "prefetch"


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


def _annotation(name):
    cls = _annotation_cls()
    if not cls:
        return None
    try:
        return cls(f"ds_tpu/{name}")
    except Exception:  # ds-lint: allow[BROADEXC] profiler annotation is decorative; the hot path must not fail on it
        return None


def profiler_span(name):
    """`with profiler_span("serve/fence.device_get"):` puts the range
    `ds_tpu/<name>` on the profiler's clock and keeps no wall time:
    for the phases inside a host call whose total a span already has.
    Near-free when no profiler is attached."""
    return _annotation(name) or contextlib.nullcontext()


class _Span:
    __slots__ = ("t0", "annotation")

    def __init__(self, name):
        self.t0 = time.perf_counter()
        self.annotation = _annotation(name)
        if self.annotation is not None:
            try:
                self.annotation.__enter__()
            except Exception:  # ds-lint: allow[BROADEXC] profiler annotation is decorative; the hot path must not fail on it
                self.annotation = None


class StepTrace:
    """start/stop named spans (timer-style, so the engine's split
    forward()/backward()/step() call sites can use it) plus a `span`
    context manager; totals drain at fences."""

    def __init__(self):
        self._open = {}
        self._lock = threading.Lock()
        self._totals = {}
        self._counts = {}
        self._export = None      # (name, t0, dur) hook -> TraceExporter

    def set_export_sink(self, fn):
        """Route every closed span to the Perfetto exporter as well
        (monitor/trace_export.py) — spans are timed once, rendered in
        both the fence metrics and the trace file."""
        self._export = fn

    def start(self, name):
        self._open[name] = _Span(name)

    def stop(self, name):
        sp = self._open.pop(name, None)
        if sp is None:
            return
        if sp.annotation is not None:
            try:
                sp.annotation.__exit__(None, None, None)
            except Exception:  # ds-lint: allow[BROADEXC] profiler annotation is decorative; the hot path must not fail on it
                pass
        dt = time.perf_counter() - sp.t0
        with self._lock:
            self._totals[name] = self._totals.get(name, 0.0) + dt
            self._counts[name] = self._counts.get(name, 0) + 1
        if self._export is not None:
            try:
                self._export(name, sp.t0, dt)
            except Exception:  # ds-lint: allow[BROADEXC] trace-export hook on the hot path; a broken exporter must not stall the step loop
                pass

    def span(self, name):
        return _SpanCtx(self, name)

    def drain(self):
        """{name: {"ms": total, "count": n, "ms_per": mean}} since the
        last drain; resets the window."""
        with self._lock:
            totals, self._totals = self._totals, {}
            counts, self._counts = self._counts, {}
        return {
            name: {"ms": round(totals[name] * 1e3, 3),
                   "count": counts.get(name, 0),
                   "ms_per": round(
                       totals[name] * 1e3 / max(counts.get(name, 1), 1),
                       3)}
            for name in totals
        }


class _SpanCtx:
    def __init__(self, trace, name):
        self._trace = trace
        self._name = name

    def __enter__(self):
        self._trace.start(self._name)
        return self

    def __exit__(self, *exc):
        self._trace.stop(self._name)
        return False
