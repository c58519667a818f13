"""The least time the chip could take for the state traffic of the
decode launches the trace holds (`ssm_costs
.decode_state_traffic_bytes`: every slot's state matrix of every layer
read once and written once, over `peaks.json`'s `hbm_bytes_per_s`),
over the device time under `state_update`, in %. Memory is the bound
that applies: a decode step does two multiply-adds a state value."""
from benchmark import kernel_costs, region_join, ssm_costs


def read(ctx):
    took = region_join.paged_state_seconds(ctx, "state_update")
    if not took:
        return None
    n = region_join.launches(ctx, r"decode")
    if not n:
        return None
    cell = ctx["cell"]
    peaks = kernel_costs.peaks_for(ctx["device"]["kind"])
    nbytes = ssm_costs.decode_state_traffic_bytes(
        cell["sizes"], cell["mix"]["inference"]["max_slots"])
    return 100.0 * n * nbytes / peaks["hbm_bytes_per_s"] / took
