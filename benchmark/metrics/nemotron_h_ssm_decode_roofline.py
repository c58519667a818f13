"""`ssm_decode_roofline`'s count for the Nemotron-H family, whose
Mamba-2 state lies in the `M` layers alone: the least time the chip
could take for the state traffic of the decode launches the trace
holds (`nemotron_h_costs.decode_state_traffic_bytes`: every slot's
state matrix of every `M` layer read once and written once, over
`peaks.json`'s `hbm_bytes_per_s`), over the device time under
`state_update`, in %. Memory is the bound that applies: a decode step
does two multiply-adds a state value."""
from benchmark import kernel_costs, nemotron_h_costs, region_join


def read(ctx):
    program = ctx["cell"]["sizes"].get("program", {})
    if program.get("architecture") != "nemotron_h":
        return None
    took = region_join.seconds(ctx, nemotron_h_costs.LAYERED,
                               nemotron_h_costs.SSM, "state_update")
    if not took:
        return None
    n = region_join.launches(ctx, r"decode")
    if not n:
        return None
    cell = ctx["cell"]
    peaks = kernel_costs.peaks_for(ctx["device"]["kind"])
    nbytes = nemotron_h_costs.decode_state_traffic_bytes(
        cell["sizes"], cell["mix"]["inference"]["max_slots"])
    return 100.0 * n * nbytes / peaks["hbm_bytes_per_s"] / took
