"""The part of `host_exposed_ms.serve` under `fence.bookkeeping`,
`admit`, `prefill.pages`, `decode.pages` and `(no phase)`: the loop's
own Python between the arrays arriving and the next dispatch."""
from benchmark import host_phases


def read(ctx):
    run = host_phases.of_run(ctx)
    return None if run is None else host_phases.exposed_ms(
        run["tail"], host_phases.BOOKKEEPING)
