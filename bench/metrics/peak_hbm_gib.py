"""Peak bytes in use on the fullest chip after the window, in GiB (the
runtime's `memory_stats`)."""


def read(ctx):
    return ctx.peak_bytes / 2**30 if ctx.peak_bytes else None
