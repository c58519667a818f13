"""The part of `host_exposed_ms.serve` under `fence.device_get`: from
the device's last operation to the host holding the arrays."""
from benchmark import host_phases


def read(ctx):
    run = host_phases.of_run(ctx)
    return None if run is None else host_phases.exposed_ms(
        run["tail"], host_phases.READBACK)
