"""Device time in `kv_gather` (the page window of every slot gathered
through the page tables, `kl[tables]`, `vl[tables]`), as % of the
traced window, decode and prefill together."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "kv_gather")
