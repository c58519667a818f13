"""Device-idle milliseconds an iteration of the traced tail while the
host was in any phase but `idle`, `(no phase)` included: what a loop
with the next block in flight would hide (`benchmark/host_phases.py`;
its three parts are the readback, the bookkeeping and the dispatch)."""
from benchmark import host_phases


def read(ctx):
    run = host_phases.of_run(ctx)
    return None if run is None else host_phases.exposed_ms(run["tail"])
