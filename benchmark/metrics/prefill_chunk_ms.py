"""Device time of one prefill program launch, median over the traced
window."""
import numpy as np
from benchmark import trace_reduce


def read(ctx):
    if ctx["trace"] is None:
        return None
    d = trace_reduce.module_durations(ctx["trace"], r"prefill")
    return 1e3 * float(np.median(d)) if d else None
