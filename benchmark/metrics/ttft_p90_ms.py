"""90th percentile of time to first token, from when each request
was due, over the requests due in the window."""
import numpy as np


def read(ctx):
    return 1e3 * float(np.percentile(ctx["ttft_s"], 90)) \
        if ctx["ttft_s"] else None
