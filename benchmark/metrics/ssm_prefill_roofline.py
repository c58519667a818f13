"""The least time the chip could take for the chunked state-space scan
of the prefill launches the trace holds (`ssm_costs
.prefill_chunk_cost`: the pairs inside a chunk, the state's read-out
and its update, against `peaks.json`), over the device time under
`ssm_chunk`, in %. The bound is whichever of the two is longer
(`kernel_costs.roofline_seconds`): at the published sizes 2.45 GFLOP a
layer and 512 tokens at the bfloat16 peak (12 us) against 17.7 MB of
state and activations (22 us), so memory, narrowly (ISSUE 31 reckoned
compute). The state's products are float32, which the matrix unit
makes in several bfloat16 passes, and the decays between are
elementwise, so this share cannot come near 100."""
from benchmark import kernel_costs, region_join, ssm_costs


def read(ctx):
    took = region_join.paged_state_seconds(ctx, "ssm_chunk")
    if not took:
        return None
    n = region_join.launches(ctx, r"prefill")
    if not n:
        return None
    cell = ctx["cell"]
    peaks = kernel_costs.peaks_for(ctx["device"]["kind"])
    cost = ssm_costs.prefill_chunk_cost(
        cell["sizes"], cell["mix"]["inference"]["prefill_chunk"],
        cell["sizes"]["mamba_chunk_size"])
    least, _ = kernel_costs.roofline_seconds(*cost, peaks)
    return 100.0 * n * least / took
