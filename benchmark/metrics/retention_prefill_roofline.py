"""The least time the chip could take for retention over the prefill
launches the trace holds (`retention_costs.prefill_chunk_cost`: the
pairs inside a chunk, phi(K)^T V into the state, phi(Q) S out of it,
against `peaks.json`), over the device time under `retention_chunk`,
in %. Compute is the bound that applies (about 53 GFLOP a layer and
512 tokens against 68 MB of state), taken at the chip's bfloat16 peak:
the state's products are float32, which the MXU makes in several
bfloat16 passes, so this share cannot come near 100."""
from benchmark import kernel_costs, retention_costs, state_scopes


def read(ctx):
    took = state_scopes.seconds(ctx, "retention_chunk")
    if not took:
        return None
    n = state_scopes.launches(ctx, r"prefill")
    if not n:
        return None
    cell = ctx["cell"]
    peaks = kernel_costs.peaks_for(ctx["device"]["kind"])
    cost = retention_costs.prefill_chunk_cost(
        cell["sizes"], cell["mix"]["inference"]["prefill_chunk"],
        cell["sizes"]["assumed"]["retention"]["chunk"])
    least, _ = kernel_costs.roofline_seconds(*cost, peaks)
    return 100.0 * n * least / took
