"""95th percentile over delivered tokens of the gap since the fence
before, shared among the tokens of one fence."""


def read(ctx):
    return ctx["itl_p95_ms"]
