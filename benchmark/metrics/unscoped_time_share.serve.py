"""Device time the join of profile and program gives no name stack for
(an instruction the compiler inserted, outside every named one): the
attribution's own failure rate, as % of the traced window."""
from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, scope_reduce.UNSCOPED)
