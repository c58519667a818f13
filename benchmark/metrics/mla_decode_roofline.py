"""The least time the chip could take for the latent rows that the
traced decode launches' attention reads and the products it makes of
them (`mla_costs.decode_attention_cost`: every cached token's
PUBLISHED row of 576 values once a layer, not the padded lanes; 64
heads' scores against the row and sums of its first 512 values; the
larger of the two bounds of `kernel_costs.roofline_seconds`), over the
device time of the decode kernel's events (`latent_decode_attention`,
one a layer and launch), in %. Memory is the bound that applies: 121
FLOP a byte where the chip's ridge is 240. It reads the same work
whatever implements the kernel, and a row padded to 640 lanes cannot
read over 90.

The cached tokens are the program's own counter on the fence rows of
the traced tail (`kv_pages_attended`: the pages the next launch walks,
summed over the live slots, x the page), mean over the tail's decode
launches; the kernel's events are the trace's own count, so a launch
that the window's edge cut is counted on both sides or on neither."""
from benchmark import kernel_costs, mla_costs, trace_reduce
from benchmark.kinds.serve_open import TRACE_ITERATIONS


def read(ctx):
    if ctx.get("trace") is None:
        return None
    from benchmark.architectures import sarvam_mla
    took, events = trace_reduce.matching_seconds(
        ctx["trace"], r"latent_decode_attention")
    every = [row for row in sarvam_mla.fence_rows(ctx)
             if row.get("iterations") and "kv_pages_attended" in row]
    if not events or not took or not every:
        return None
    tail = every[-TRACE_ITERATIONS:]
    # the fence reports the pages of ONE launch: a row's launches x that
    pages = sum(r["iterations"] * r["kv_pages_attended"] for r in tail) / \
        sum(r["iterations"] for r in tail)
    page = ctx["cell"]["mix"]["inference"]["kv_cache"]["page_size"]
    flops, nbytes = mla_costs.decode_attention_cost(
        ctx["cell"]["sizes"], events * pages * page)
    least, _ = kernel_costs.roofline_seconds(
        flops, nbytes, kernel_costs.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least / took
