"""Device time in `gmu` (the gated memory units: the gate's
projection, its product with the memory that layer L/2 made in the
same launch, the output projection), as % of the traced window. None
for a program without the region."""
from benchmark import phi4flash_regions


def read(ctx):
    return phi4flash_regions.share(ctx, "gmu")
