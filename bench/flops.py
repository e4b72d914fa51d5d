"""Operations and bytes from shapes, for the model-FLOP utilization and the
kernels' roofline shares. `dims` is a configuration's "model" entry.
"""
from __future__ import annotations


def layer_matmul_params(dims: dict) -> int:
    """Weights of one decoder layer that enter a matrix product: q, k, v,
    o and the three SwiGLU matrices."""
    d, h, kv, f = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                   dims["d_ff"])
    hd = d // h
    return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f


def matmul_params(dims: dict) -> int:
    """Parameters used in a matrix product per token: the layers and the
    unembedding (an embedding lookup is not a product)."""
    return (dims["n_layers"] * layer_matmul_params(dims)
            + dims["vocab_size"] * dims["d_model"])


def param_count(dims: dict) -> int:
    """Every trained parameter; non-parametric LayerNorms add none, and a
    tied embedding is the unembedding."""
    untied = 0 if dims["tie_embeddings"] else dims["vocab_size"] * dims[
        "d_model"]
    return matmul_params(dims) + untied


def attention_flops_per_token(dims: dict, seq: int) -> float:
    """Forward causal attention per token over all layers: the score and
    value products over (seq + 1) / 2 keys on average."""
    d = dims["d_model"]
    return 2 * 2 * d * (seq + 1) / 2 * dims["n_layers"]


def train_flops_per_token(dims: dict, seq: int) -> float:
    """Forward and backward (3x forward) per token, recomputation excluded."""
    return 6 * matmul_params(dims) + 3 * attention_flops_per_token(dims, seq)


def step_flops(dims: dict, seq: int, rows: int, ascent_rows: int) -> float:
    """Model FLOPs of one training step: a forward and backward pass over
    the descent rows and, for AsyncSAM, over the ascent rows."""
    return (rows + ascent_rows) * seq * train_flops_per_token(dims, seq)


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
