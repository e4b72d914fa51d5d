"""Host time per step in the training loop outside the step call and the
data wait: `Engine.fit`'s own work and its callbacks (bench host clock)."""


def read(ctx):
    w = ctx.window
    rest = ctx.window_s - sum(w["step_s"]) - sum(w["waits"])
    return 1e3 * rest / ctx.steps
