"""Device time by region of the serving programs of a model whose
layers keep DIFFERENT things (`deepspeed_tpu/utils/scopes.py`,
`SCOPES_HYBRID`): `region_join.py`'s join with this vocabulary. It is
the benchmark's own copy (a test holds the two equal); a program
without `shared_kv` or `gmu` (every other cell, the parent commit)
gives None, which is not 0%."""
from benchmark import region_join, trace_reduce

NEW = ("shared_kv", "gmu")
HYBRID = region_join.PAGED_STATE[:-3] + NEW + region_join.PAGED_STATE[-3:]


def seconds(ctx, *wanted):
    return region_join.seconds(ctx, HYBRID, NEW, *wanted)


def share(ctx, *wanted):
    """`seconds` as % of the traced window."""
    secs = seconds(ctx, *wanted)
    if secs is None:
        return None
    return 100.0 * secs / trace_reduce.window_seconds(ctx["trace"])
