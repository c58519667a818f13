"""The host's own milliseconds of a serving iteration, mean over the
iterations of the timed window: every phase of the loop's own spans
but the wait inside `fence.device_get` and the wait for arrivals
(`idle`), self times on the host's clock (`benchmark/host_phases.py`)."""
from benchmark import host_phases


def read(ctx):
    run = host_phases.of_run(ctx)
    return None if run is None else host_phases.host_iter_ms(run["window"])
