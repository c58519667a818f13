"""Device time in the serving programs' layer scan outside every
inner region (`layers` with no `attn_qkv`, `kv_write`, `kv_gather`,
`attn`, `attn_out` or `mlp` below it): each layer's K/V page pool
sliced out of the stacked pools and written back. % of the traced
window, decode and prefill together."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "layers")
