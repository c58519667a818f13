"""Median time between the ends of successive steps, on the
benchmark's clock (`block_until_ready`)."""
import numpy as np


def read(ctx):
    return 1e3 * float(np.median(ctx["step_seconds"])) \
        if ctx["step_seconds"] else None
