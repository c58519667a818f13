"""`moe_held_roofline`'s count for the Nemotron-H family, whose expert
is TWO matrices: the least time the chip could take for the held
experts' matrices that the traced launches' grouped products read
(`nemotron_h_costs.experts_traffic_bytes`: every held expert with at
least one row read once, both its matrices at the width they are
stored at, the share's rows in and out; over `peaks.json`'s
`hbm_bytes_per_s`), over the device time under `moe_experts`, in %.
Memory is the bound that applies: an expert of 10M parameters sees a
handful of rows.

The experts touched and the rows are the program's own counters on the
fence rows of the traced tail, a launch of the decode program and a
launch of prefill each; the launches are the trace's own count."""
from benchmark import kernel_costs, nemotron_h_costs, region_join
from benchmark.kinds.serve_open import TRACE_ITERATIONS


def read(ctx):
    from benchmark.architectures import nemotron_h
    every = nemotron_h.fence_rows(ctx)
    if not every:
        return None
    took = region_join.seconds(ctx, nemotron_h_costs.LAYERED,
                               nemotron_h_costs.MOE, "moe_experts")
    if not took:
        return None
    tail = every[-TRACE_ITERATIONS:]
    touched = n_rows = 0.0
    for pattern, launches, prefix in (
            (r"decode", "iterations", ""),
            (r"prefill", "prefill_launches", "prefill_")):
        n = region_join.launches(ctx, pattern)
        keys = (prefix + "moe_experts_touched", prefix + "moe_rows")
        # a tail without such a launch in its rows: the run's mean
        each = nemotron_h.per_launch(tail, launches, *keys) or \
            nemotron_h.per_launch(every, launches, *keys)
        if n and each is None:
            return None
        if n:
            touched, n_rows = touched + n * each[0], n_rows + n * each[1]
    if not touched:
        return None
    peaks = kernel_costs.peaks_for(ctx["device"]["kind"])
    nbytes = nemotron_h_costs.experts_traffic_bytes(ctx["cell"]["sizes"],
                                                    touched, n_rows)
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / took
