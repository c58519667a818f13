"""The least time the chip could take for the pages that the traced
decode launches' attention over the SHARED pool reads
(`phi4flash_costs.shared_decode_bytes`: a live slot's page once a
reading layer and launch, K and V, the published 1,280 values a token
in each, over `peaks.json`'s `hbm_bytes_per_s`), over the device time
of that kernel's events (`shared_kv_decode_attention`, one a reading
layer and launch), in %. Memory is the bound that applies: the kernel
makes 40 x 1,280 products a key where 2 x 1,280 x 2 bytes arrive, 10
FLOP a byte against the chip's ridge of 240.

The page reads are the program's own counter on the fence rows of the
traced tail (`kv_pages_shared_attended`: the pages the next launch
reads, the live slots' pages x the reading layers), mean over the
tail's decode launches; the kernel's events are the trace's own count
(one a reading layer and launch), so a launch that the window's edge
cut is counted on both sides or on neither."""
from benchmark import kernel_costs, phi4flash_costs, trace_reduce
from benchmark.kinds.serve_open import TRACE_ITERATIONS


def read(ctx):
    if ctx.get("trace") is None:
        return None
    from benchmark.architectures import phi4flash
    took, events = trace_reduce.matching_seconds(
        ctx["trace"], r"shared_kv_decode_attention")
    every = [row for row in phi4flash.fence_rows(ctx)
             if row.get("iterations") and "kv_pages_shared_attended" in row]
    if not events or not took or not every:
        return None
    tail = every[-TRACE_ITERATIONS:]
    sizes = ctx["cell"]["sizes"]
    # the fence reports the reads of ONE launch, every reading layer's
    a_launch = sum(r["iterations"] * r["kv_pages_shared_attended"]
                   for r in tail) / sum(r["iterations"] for r in tail)
    reads = events * a_launch / phi4flash_costs.shared_readers(sizes)
    page = ctx["cell"]["mix"]["inference"]["kv_cache"]["page_size"]
    nbytes = phi4flash_costs.shared_decode_bytes(sizes, reads, page)
    peaks = kernel_costs.peaks_for(ctx["device"]["kind"])
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / took
