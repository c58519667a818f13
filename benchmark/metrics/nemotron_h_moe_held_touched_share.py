"""Of the routed experts this chip HOLDS of every expert layer of a
Nemotron-H configuration (its `n_routed_experts`, a share of the
published count, over the `E` layers of its pattern), the share that a
decode launch's rows touch (the program's counter
`moe_experts_touched`), mean over the decode launches of the window,
in %. It says what `nemotron_h_moe_held_roofline`'s bytes were and
whether two seeds do the same work; it is not a score."""
from benchmark import nemotron_h_costs


def read(ctx):
    from benchmark.architectures import nemotron_h
    rows = nemotron_h.window_rows(ctx)
    return nemotron_h.touched_share(
        rows, nemotron_h_costs.experts_held(ctx["cell"]["sizes"])) \
        if rows else None
