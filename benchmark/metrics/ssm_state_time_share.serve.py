"""Device time in the regions that touch a state-space mixer's state
(`state_update`: decode's one-token step of every slot; `ssm_chunk`:
prefill's chunked scan; `ssm_conv`: the causal convolution and its
carried rows; `state_reset`), as % of the traced window, decode and
prefill together."""
from benchmark import region_join, trace_reduce


def read(ctx):
    secs = region_join.paged_state_seconds(ctx, *region_join.SSM)
    if secs is None:
        return None
    return 100.0 * secs / trace_reduce.window_seconds(ctx["trace"])
