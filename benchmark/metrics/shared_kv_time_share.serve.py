"""Device time in `shared_kv` (the attention of the layers that read
the ONE layer of pages another layer wrote: the decode kernel's walk
and the little round it), as % of the traced window. None for a
program without the region."""
from benchmark import phi4flash_regions


def read(ctx):
    return phi4flash_regions.share(ctx, "shared_kv")
