"""6 N tokens/s over chips x the bf16 peak of `peaks.json`, in %.
Recomputed operations and attention's own are not counted."""
from benchmark import kernel_costs


def read(ctx):
    peaks = kernel_costs.peaks_for(ctx["device"]["kind"])
    flops = kernel_costs.train_flops_per_token(ctx["cell"]["sizes"])
    return 100.0 * flops * ctx["tokens_per_s_per_chip"] / peaks["bf16_flops"]
