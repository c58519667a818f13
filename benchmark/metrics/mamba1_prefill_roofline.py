"""The least time the chip could take for the selective scan of the
prefill launches the trace holds (`phi4flash_costs.prefill_scan_cost`
against `peaks.json`), over the device time under `ssm_chunk`, in %.
The share is of the MEMORY roofline and low by construction: the scan
is six operations and an exponential for each of tokens x 5,120 x 16
values on the vector unit, which the matrix unit's peak does not
describe (1.3 us a layer by that peak against 26 us for 21 MB of
activations), and the vector unit, not memory, bounds the kernel. None
where the traced tail holds no prefill launch, or for a program
without this family's regions: the metric is listed for a cell only if
a prefill launch lies in the traced tail on every seed."""
from benchmark import kernel_costs, phi4flash_costs, phi4flash_regions, \
    region_join


def read(ctx):
    took = phi4flash_regions.seconds(ctx, "ssm_chunk")
    if not took:
        return None
    n = region_join.launches(ctx, r"prefill")
    if not n:
        return None
    cell = ctx["cell"]
    peaks = kernel_costs.peaks_for(ctx["device"]["kind"])
    cost = phi4flash_costs.prefill_scan_cost(
        cell["sizes"], cell["mix"]["inference"]["prefill_chunk"])
    least, _ = kernel_costs.roofline_seconds(*cost, peaks)
    return 100.0 * n * least / took
