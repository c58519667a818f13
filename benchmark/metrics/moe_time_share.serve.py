"""Device time in the expert layer's five regions (`moe_router`,
`moe_dispatch`: the rows sorted by expert and gathered, `moe_experts`:
the grouped products, `moe_shared`, `moe_combine`), as % of the traced
window, decode and prefill together."""
from benchmark import moe_costs, region_join, trace_reduce


def read(ctx):
    secs = region_join.seconds(ctx, moe_costs.PAGED_MOE, moe_costs.MOE,
                               *moe_costs.MOE)
    if secs is None:
        return None
    return 100.0 * secs / trace_reduce.window_seconds(ctx["trace"])
