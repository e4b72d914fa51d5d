"""OLMo's decoder (arXiv:2402.00838): its weights, its plain reference and
its FLOP count, independent of the program.

A configuration names this module with `"arch": "olmo"`; `dims` is its
"model" entry (with "norm_eps" for `loss`).

- `init_params`: the parameter tree in the layout the program trains
  (stacked layers under "blocks", tied embedding under "embedding"), with
  plain jax.random calls, in float32 as the configuration trains it.
- `loss`: non-parametric LayerNorm, rotary embeddings on half splits,
  SwiGLU MLP, tied embedding, causal softmax attention, and the next-token
  cross entropy. Straight `jax.numpy`, every matrix product from
  `bench.matmul` (float32 at HIGHEST, or the fp8 control), the layers
  under one `lax.scan`, each recomputed in the backward pass. Scanned, not
  unrolled: unrolled, the whole 16-layer model's step is a 288 MB
  executable, more than a compile cache capped at 192 MiB keeps, and a run
  compiled it anew for some 200 s (v5e host).
- `matmul_params`, `param_count`, `train_flops_per_token`: a dense
  decoder's counts from its widths.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.matmul import EINSUMS


# --- weights -----------------------------------------------------------------

def init_params(key: jax.Array, dims: dict) -> dict:
    """Fan-in scaled normal weights."""
    d, h, kv = dims["d_model"], dims["n_heads"], dims["n_kv_heads"]
    hd = d // h
    n, f, v = dims["n_layers"], dims["d_ff"], dims["vocab_size"]
    ks = iter(jax.random.split(key, 8))

    def dense(k, shape, fan_in, scale=1.0):
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
                * (scale / math.sqrt(fan_in)))

    attn = {"wq": dense(next(ks), (n, d, h * hd), d),
            "wk": dense(next(ks), (n, d, kv * hd), d),
            "wv": dense(next(ks), (n, d, kv * hd), d),
            "wo": dense(next(ks), (n, h * hd, d), h * hd,
                        1.0 / math.sqrt(2 * n))}
    mlp = {"wi": dense(next(ks), (n, d, f), d),
           "wg": dense(next(ks), (n, d, f), d),
           "wo_mlp": dense(next(ks), (n, f, d), f)}
    embed = 0.02 * jax.random.normal(next(ks), (v, d), jnp.float32)
    return {"embedding": {"embed": embed}, "final_norm": {},
            "blocks": {"ln1": {}, "ln2": {}, "attn": attn, "mlp": mlp}}


# --- the plain reference -----------------------------------------------------

def _layer_norm(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(x, p, dims, es):
    b, s, d = x.shape
    h, kv = dims["n_heads"], dims["n_kv_heads"]
    hd = d // h
    eps = dims["norm_eps"]
    y = _layer_norm(x, eps)
    q = es("bsd,dh->bsh", y, p["attn"]["wq"]).reshape(b, s, h, hd)
    k = es("bsd,dh->bsh", y, p["attn"]["wk"]).reshape(b, s, kv, hd)
    v = es("bsd,dh->bsh", y, p["attn"]["wv"]).reshape(b, s, kv, hd)
    q, k = _rope(q, dims["rope_theta"]), _rope(k, dims["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = es("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = es("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + es("bsh,hd->bsd", att.reshape(b, s, h * hd), p["attn"]["wo"])
    y = _layer_norm(x, eps)
    m = p["mlp"]
    hid = (jax.nn.silu(es("bsd,df->bsf", y, m["wi"]))
           * es("bsd,df->bsf", y, m["wg"]))
    return x + es("bsf,fd->bsd", hid, m["wo_mlp"])


def loss(params: dict, batch: dict, dims: dict, precision: str = "fp32"):
    """Mean next-token cross entropy over the labels that are not -1."""
    es = EINSUMS[precision]
    embed = params["embedding"]["embed"]
    x = embed[batch["tokens"]]
    block = jax.checkpoint(functools.partial(_block, dims=dims, es=es))
    x, _ = jax.lax.scan(lambda x, p: (block(x, p), None), x,
                        params["blocks"])
    x = _layer_norm(x, dims["norm_eps"])
    logits = es("bsd,vd->bsv", x, embed)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                                 axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --- counts ------------------------------------------------------------------

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
