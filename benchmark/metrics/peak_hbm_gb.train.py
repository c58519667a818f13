"""`memory_stats()["peak_bytes_in_use"]` after the window, the
fullest device, in GB (1e9 bytes)."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9
