"""Share of the HBM roofline reached by the weight-space kernels of the
fused bucket-resident path (kernels/fused_update.py, kernels/sam_perturb.py:
the AdamW epilogue, the perturbation axpy, the clip's squared norm and the
ascent refresh's dot and norms): the bytes of each call's operands and
results, read or written once, over the calls' device time. They are the
Pallas calls whose operands are all flat buffers (rank 1, or rows of 128)
of at least 2**20 elements among them; bytes bound them (device trace)."""
from bench import flops, trace

BUCKET = 2**20


def _is_epilogue(call) -> bool:
    shapes = [s for _, s in call.operands]
    flat = all(len(s) == 1 or (len(s) == 2 and s[1] == 128) for s in shapes)
    big = any(_size(s) >= BUCKET for s in shapes)
    return flat and big


def _size(shape) -> int:
    n = 1
    for x in shape:
        n *= x
    return n


def read(ctx):
    nbytes = seconds = 0.0
    for ops in ctx.trace.devices.values():
        for call in trace.custom_calls(ops):
            if _is_epilogue(call):
                nbytes += call.nbytes
                seconds += trace.summed_s([call.op])
    if not seconds:
        return None
    share, _ = flops.roofline_share(0.0, nbytes, seconds,
                                    ctx.peaks.bf16_flops, ctx.peaks.hbm_bytes)
    return share
