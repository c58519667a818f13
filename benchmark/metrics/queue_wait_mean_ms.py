"""Mean of admitted_at - arrival_time over the requests due in the
window."""
import numpy as np


def read(ctx):
    return 1e3 * float(np.mean(ctx["queue_wait_s"])) \
        if ctx["queue_wait_s"] else None
