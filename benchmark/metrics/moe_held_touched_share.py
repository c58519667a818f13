"""Of the routed experts this chip HOLDS of every expert layer (the
configuration's `num_experts`, a share of the published count), the
share that a decode launch's rows touch (the program's counter
`moe_experts_touched`: distinct held experts with at least one row,
summed over the expert layers, over held experts x expert layers),
mean over the decode launches of the window, in %. The number that
says whether two seeds do the same work: a grouped product reads the
experts its rows picked."""
from benchmark import mla_costs


def read(ctx):
    from benchmark.architectures import sarvam_mla
    rows = sarvam_mla.window_rows(ctx)
    return sarvam_mla.touched_share(
        rows, mla_costs.experts_held(ctx["cell"]["sizes"])) if rows else None
