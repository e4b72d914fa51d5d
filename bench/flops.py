"""Operations and bytes from shapes, for the model-FLOP utilization and the
kernels' roofline shares. A model's own counts are its architecture's
(`bench/archs/<arch>.py`); `dims` is a configuration's "model" entry.
"""
from __future__ import annotations

from types import ModuleType


def step_flops(arch: ModuleType, dims: dict, seq: int, rows: int,
               ascent_rows: int) -> float:
    """Model FLOPs of one training step: a forward and backward pass over
    the descent rows and, for AsyncSAM, over the ascent rows."""
    return (rows + ascent_rows) * seq * arch.train_flops_per_token(dims, seq)


def attn_fwd_flops(b: int, s: int, h: int, hd: int) -> float:
    """One causal flash-attention forward call: q k^T and p v over the
    s (s + 1) / 2 visible (query, key) pairs of each of b * h heads."""
    return 2 * 2 * hd * s * (s + 1) / 2 * b * h


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes: float) -> tuple[float, str]:
    """(percent of the roofline, "compute" or "memory"): the least time the
    chip could take over the time taken."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bytes
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
