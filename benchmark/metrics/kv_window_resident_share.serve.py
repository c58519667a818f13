"""Pages that the window layers' pool holds, over the pages it would
hold had those layers kept whole histories (held + what the live
slots gave back as their windows passed: the program's counters
`kv_pages_window_in_use` and `kv_pages_window_released`), mean over
the window's fences, in %."""


def read(ctx):
    from benchmark.architectures import afmoe
    return afmoe.window_resident_share(afmoe.window_rows(ctx))
