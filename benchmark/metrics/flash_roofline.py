"""The least time the chip could take for the flash-attention forward
and backward kernels the trace holds (`kernel_costs.flash_causal_cost`
against `peaks.json`), over the time they took, in %. At head width 64
and sequence 1024 compute is the bound that applies."""
from benchmark import kernel_costs, trace_reduce


def read(ctx):
    if ctx["trace"] is None:
        return None
    sizes = ctx["cell"]["sizes"]
    peaks = kernel_costs.peaks_for(ctx["device"]["kind"])
    cost = kernel_costs.flash_causal_cost(
        ctx["rows_per_chip"], sizes["n_head"], ctx["seq"],
        sizes["n_embd"] // sizes["n_head"])
    least = took = 0.0
    for which, pattern in (("fwd", r"flash_fwd"), ("bwd", r"flash_bwd")):
        secs, launches = trace_reduce.matching_seconds(ctx["trace"], pattern)
        if not launches:
            return None
        bound, _ = kernel_costs.roofline_seconds(*cost[which], peaks)
        least += bound * launches
        took += secs
    return 100.0 * least / took
