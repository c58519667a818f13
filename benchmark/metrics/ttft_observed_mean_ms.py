"""Due time to the fence that delivers the first token, mean over the
requests due in the window. Recorded on every PR and judging none: one
request caught by the iteration before or after moves the mean of 28
by 1.2%, and runs of one tree spread by 6-8% (PERF.md, PR 23)."""


def read(ctx):
    return ctx["ttft_mean_ms"]
