"""The least time the chip could take for the expert matrices that
the traced launches' grouped products read (`moe_costs
.experts_traffic_bytes`: every expert with at least one row read once,
the rows in and out; over `peaks.json`'s `hbm_bytes_per_s`), over the
device time under `moe_experts`, in %. Memory is the bound that
applies: an expert sees a handful of rows. It reads the same work
whatever implements the product.

The experts touched and the rows are the program's own counters on the
fence rows of the traced tail (the last `TRACE_ITERATIONS` iterations
of the loop and so the last that many rows), a launch of the decode
program and a launch of prefill each; the launches are the trace's
own count, so a launch that the window's edge cut is not counted on
either side."""
from benchmark import kernel_costs, moe_costs, region_join
from benchmark.kinds.serve_open import TRACE_ITERATIONS


def per_launch(rows, launches, prefix):
    """(experts touched, (token, pick) rows) a launch over `rows`."""
    n = sum(r[launches] for r in rows)
    if not n:
        return None
    return (sum(r[prefix + "moe_experts_touched"] for r in rows) / n,
            sum(r[prefix + "moe_rows"] for r in rows) / n)


def read(ctx):
    took = region_join.seconds(ctx, moe_costs.PAGED_MOE, moe_costs.MOE,
                               "moe_experts")
    if not took:
        return None
    from benchmark.architectures import afmoe
    every = [row for row in afmoe.fence_rows(ctx)
             if "moe_experts_touched" in row]
    tail = every[-TRACE_ITERATIONS:]
    touched = n_rows = 0.0
    for pattern, launches, prefix in (
            (r"decode", "iterations", ""),
            (r"prefill", "prefill_launches", "prefill_")):
        n = region_join.launches(ctx, pattern)
        # a tail without such a launch in its rows: the run's mean
        each = per_launch(tail, launches, prefix) or \
            per_launch(every, launches, prefix)
        if n and each is None:
            return None
        if n:
            touched, n_rows = touched + n * each[0], n_rows + n * each[1]
    if not touched:
        return None
    peaks = kernel_costs.peaks_for(ctx["device"]["kind"])
    nbytes = moe_costs.experts_traffic_bytes(ctx["cell"]["sizes"], touched,
                                             n_rows)
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / took
