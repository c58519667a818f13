"""Compile requests inside the measured window (jax monitoring
events); must read 0."""


def read(ctx):
    return ctx["window_compiles"]["requests"]
