"""Device time in the regions that read the weights (`attn_qkv`,
`attn_out`, `mlp`, `head`: every projection of the model with its
LayerNorm and epilogue), as % of the traced window, decode and prefill
together."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "attn_qkv", "attn_out", "mlp", "head")
