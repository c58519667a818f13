"""1 - union of device operations over the traced window, in %."""
from benchmark import trace_reduce


def read(ctx):
    if ctx["trace"] is None:
        return None
    window = trace_reduce.window_seconds(ctx["trace"])
    return 100.0 * (1.0 - trace_reduce.busy_seconds(ctx["trace"]) / window)
