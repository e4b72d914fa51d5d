"""Plain reference of what a training cell computes, independent of the program.

An OLMo-style decoder (non-parametric LayerNorm, rotary embeddings on half
splits, SwiGLU MLP, tied embedding, causal softmax attention), its next-token
cross entropy, and the method's step around it: `sgd` (the plain gradient)
or `async_sam` (descent gradient at w + rho * a / ||a|| with the ascent
gradient a of the previous step, taken on a separate ascent batch at the
unperturbed weights; no perturbation on the first step), then AdamW with a
global-norm clip. Straight `jax.numpy`, float32, every matrix product at
HIGHEST precision, each layer recomputed in the backward pass so that it fits
beside nothing else on one chip.

`precision="fp8"` is the control: the same computation with every matrix
product's operands rounded to float8 (e4m3 forward, e5m2 cotangents, one
scale per tensor), the step below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# --- matrix products ---------------------------------------------------------

def _einsum32(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _round_fp8(x, dtype):
    """Round to `dtype` under one per-tensor scale, back to float32."""
    x = x.astype(jnp.float32)
    top = jnp.float32(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _einsum_fp8(spec: str, a, b):
    ins, out = spec.split("->")
    sa, sb = ins.split(",")

    @jax.custom_vjp
    def f(a, b):
        return _einsum32(spec, _round_fp8(a, jnp.float8_e4m3fn),
                         _round_fp8(b, jnp.float8_e4m3fn))

    def fwd(a, b):
        qa = _round_fp8(a, jnp.float8_e4m3fn)
        qb = _round_fp8(b, jnp.float8_e4m3fn)
        return _einsum32(spec, qa, qb), (qa, qb)

    def bwd(res, g):
        qa, qb = res
        qg = _round_fp8(g, jnp.float8_e5m2)
        da = _einsum32(f"{out},{sb}->{sa}", qg, qb)
        db = _einsum32(f"{sa},{out}->{sb}", qa, qg)
        return da, db

    f.defvjp(fwd, bwd)
    return f(a, b)


EINSUMS: dict[str, Callable] = {"fp32": _einsum32, "fp8": _einsum_fp8}


# --- the model ---------------------------------------------------------------

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
    blocks = params["blocks"]
    block = jax.checkpoint(functools.partial(_block, dims=dims, es=es))
    for i in range(dims["n_layers"]):
        x = block(x, jax.tree.map(lambda a: a[i], blocks))
    x = _layer_norm(x, dims["norm_eps"])
    logits = es("bsd,vd->bsv", x, embed)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                                 axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --- the step ----------------------------------------------------------------

def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree.leaves(tree)))


class State(NamedTuple):
    params: dict
    mu: dict
    nu: dict
    count: jax.Array
    ascent: dict           # async_sam: the previous step's ascent gradient
    have_ascent: jax.Array


def init_state(params: dict) -> State:
    def zeros():
        return jax.tree.map(jnp.zeros_like, params)

    return State(params, zeros(), zeros(), jnp.zeros((), jnp.int32), zeros(),
                 jnp.zeros((), bool))


def make_step(dims: dict, train: dict, precision: str = "fp32"):
    """(state, batch) -> (state, loss, clipped gradient) for one step of
    `train` ({"method", "rho", "lr", "clip_norm", "weight_decay", "b1",
    "b2", "eps"})."""
    lossf = functools.partial(loss, dims=dims, precision=precision)
    vg = jax.value_and_grad(lossf)

    def step(st: State, batch: dict):
        descent = {k: batch[k] for k in ("tokens", "labels")}
        if train["method"] == "async_sam":
            rho = jnp.where(st.have_ascent, train["rho"], 0.0)
            scale = rho / (global_norm(st.ascent) + 1e-12)
            w = jax.tree.map(lambda p, a: p + scale * a, st.params, st.ascent)
            value, g = vg(w, descent)
            ascent = jax.grad(lossf)(st.params, batch["ascent"])
            have = jnp.ones((), bool)
        elif train["method"] == "sgd":
            value, g = vg(st.params, descent)
            ascent, have = st.ascent, st.have_ascent
        else:
            raise ValueError(f"no reference for method {train['method']!r}")
        gn = global_norm(g)
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, train["clip_norm"] / (gn + 1e-12)),
            g)
        b1, b2 = train["b1"], train["b2"]
        count = st.count + 1
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, st.mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, st.nu, g)
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, m, v: p - train["lr"] * (
                (m / c1) / (jnp.sqrt(v / c2) + train["eps"])
                + train["weight_decay"] * p),
            st.params, mu, nu)
        return State(params, mu, nu, count, ascent, have), value, g

    return step
