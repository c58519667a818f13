"""Device time in `mla_absorb` (the two per-head products with W_kvb
that latent attention's absorbed form adds: W^K into every head's
query, W^V out of the attended latent rows), as % of the traced
window, decode and prefill together."""
from benchmark import mla_costs, region_join, trace_reduce


def read(ctx):
    secs = region_join.seconds(ctx, mla_costs.LATENT_MOE, mla_costs.ABSORB,
                               *mla_costs.ABSORB)
    if secs is None:
        return None
    return 100.0 * secs / trace_reduce.window_seconds(ctx["trace"])
