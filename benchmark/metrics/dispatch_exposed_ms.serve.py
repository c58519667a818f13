"""The part of `host_exposed_ms.serve` under `prefill.dispatch`,
`activate` with its two children and `decode.dispatch`: until the
device's first operation starts."""
from benchmark import host_phases


def read(ctx):
    run = host_phases.of_run(ctx)
    return None if run is None else host_phases.exposed_ms(
        run["tail"], host_phases.DISPATCH)
