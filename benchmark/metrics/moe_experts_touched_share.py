"""Of the experts the expert layers hold, the share that a decode
launch's rows touch (the program's counter `moe_experts_touched`:
distinct experts with at least one row, summed over the expert layers,
over experts x expert layers), mean over the decode launches of the
window, in %. The number that says whether two seeds do the same work:
a grouped product reads the experts its rows picked."""
from benchmark import moe_costs


def read(ctx):
    from benchmark.architectures import afmoe
    rows, sizes = afmoe.window_rows(ctx), ctx["cell"]["sizes"]
    return afmoe.touched_share(
        rows, sizes["num_experts"] * moe_costs.expert_layers(sizes)) \
        if rows else None
