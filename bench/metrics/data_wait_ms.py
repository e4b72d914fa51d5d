"""Time per step the loop waited on the pipeline's `next` (bench host
clock around the iterator `Engine.fit` draws from)."""


def read(ctx):
    waits = ctx.window["waits"]
    return 1e3 * sum(waits) / len(waits) if waits else None
