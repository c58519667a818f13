"""Programs the persistent cache could not answer during set-up (jax
monitoring events); 0 in a warm run."""


def read(ctx):
    return ctx["setup_compiles"]["cache_misses"]
