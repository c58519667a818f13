"""Device time in `attn` (`paged_attention`: scores, softmax, values),
as % of the traced window, decode and prefill together."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "attn")
