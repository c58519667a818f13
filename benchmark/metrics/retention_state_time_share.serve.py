"""Device time in the regions that touch the recurrent state
(`state_update`: decode's update and read-out; `retention_chunk`:
prefill's chunked form; `state_reset`), as % of the traced window,
decode and prefill together."""
from benchmark import state_scopes, trace_reduce


def read(ctx):
    secs = state_scopes.seconds(ctx, *state_scopes.STATE)
    if secs is None:
        return None
    return 100.0 * secs / trace_reduce.window_seconds(ctx["trace"])
