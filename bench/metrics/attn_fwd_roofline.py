"""Share of the roofline reached by the flash-attention forward kernel
(kernels/flash_attention.py): its causal FLOPs and the bytes of q, k, v and
the output, from each call's own shapes, over the calls' device time, taken
in every call in the window (the forward and its recomputation under
remat). The kernel is the Pallas call whose three operands and result are
all [batch x heads, seq, head_dim] (device trace)."""
from bench import flops, trace


def _is_attention(call) -> bool:
    shapes = [s for _, s in call.operands]
    out = [s for _, s in call.results]
    return (len(shapes) == 3 and len(out) == 1 and len(out[0]) == 3
            and all(len(s) == 3 and s[1:] == out[0][1:] for s in shapes))


def read(ctx):
    done = nbytes = seconds = 0.0
    for ops in ctx.trace.devices.values():
        for call in trace.custom_calls(ops):
            if not _is_attention(call):
                continue
            bh, s, hd = call.results[0][1]
            done += flops.attn_fwd_flops(1, s, bh, hd)
            nbytes += call.nbytes
            seconds += trace.summed_s([call.op])
    if not seconds:
        return None
    share, _ = flops.roofline_share(done, nbytes, seconds,
                                    ctx.peaks.bf16_flops, ctx.peaks.hbm_bytes)
    return share
