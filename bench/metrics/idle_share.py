"""Share of the traced window in which no operation ran on the idlest chip
(device trace)."""
from bench import trace


def read(ctx):
    if not ctx.trace.devices:
        return None
    return 100.0 * trace.idle_share(ctx.trace)
