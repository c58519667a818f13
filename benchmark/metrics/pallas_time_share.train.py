"""Device time inside the Pallas kernels (found by the `name=` each
carries), as % of the traced window."""
from benchmark import trace_reduce

KERNELS = r"flash_|fused_bias_|quantized_|block_sparse|moe_"


def read(ctx):
    if ctx["trace"] is None:
        return None
    secs, count = trace_reduce.matching_seconds(ctx["trace"], KERNELS)
    if not count:
        return None
    return 100.0 * secs / trace_reduce.window_seconds(ctx["trace"])
