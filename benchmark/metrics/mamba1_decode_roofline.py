"""The least time the chip could take for the Mamba-1 states of the
decode launches the trace holds (`phi4flash_costs
.decode_state_traffic_bytes`: every slot's [d_inner, 16] float32 state
of every layer that keeps one read once and written once, over
`peaks.json`'s `hbm_bytes_per_s`), over the device time under
`state_update` (decode's step alone stands there), in %. Memory is the
bound that applies: a step makes six operations a state value. None
for a program without this family's regions."""
from benchmark import kernel_costs, phi4flash_costs, phi4flash_regions, \
    region_join


def read(ctx):
    took = phi4flash_regions.seconds(ctx, "state_update")
    if not took:
        return None
    n = region_join.launches(ctx, r"decode")
    if not n:
        return None
    cell = ctx["cell"]
    peaks = kernel_costs.peaks_for(ctx["device"]["kind"])
    nbytes = phi4flash_costs.decode_state_traffic_bytes(
        cell["sizes"], cell["mix"]["inference"]["max_slots"])
    return 100.0 * n * nbytes / peaks["hbm_bytes_per_s"] / took
