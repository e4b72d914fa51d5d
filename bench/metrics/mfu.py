"""Model FLOP utilization of the whole step: the FLOPs of every forward and
backward pass the method needs (recomputation excluded; the architecture's
count, `bench/archs/<arch>.py`) over the window's time, chips and the chip's
bf16 peak (host clock, shapes)."""
from bench import flops


def read(ctx):
    per_step = flops.step_flops(ctx.arch, ctx.dims, ctx.seq, ctx.rows,
                                ctx.ascent_rows)
    return 100.0 * per_step * ctx.steps / (
        ctx.window_s * ctx.chips * ctx.peaks.bf16_flops)
