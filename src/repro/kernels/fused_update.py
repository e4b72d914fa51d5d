"""Pallas TPU kernels for the fused weight-space epilogue.

The per-step epilogue — clip scale, weight decay, momentum/Adam, lr scale,
apply — runs as ~6-10 per-leaf jnp passes in the unfused path, each
re-streaming every parameter element through HBM. These kernels collapse the
whole optimizer tail into ONE pass per dtype bucket: read (w, g, state),
write (w', state'), everything else lives in VMEM registers.

  sgd_epilogue     w' = w - lr * d,  d = nesterov/momentum(clip*g + wd*w)
  adamw_epilogue   w' = w - lr * ((mu'/c1)/(sqrt(nu'/c2)+eps) + wd*w)
  fused_axpy       out = y + alpha * x          (the SAM perturbation axpy)
  fused_dot_norms  (<a,b>, ||a||^2, ||b||^2)    (AsyncSAM ascent refresh)
  delta_amax       max|p - s + e|               (JOB-delta int8 scale probe)
  delta_encode_i8  q = int8((p-s+e)/scale); s' = s + scale*q; e' = d - scale*q
                   (the remote lane's delta+quantize JOB encoding: one read
                   pass over the resident param / shadow / residual buckets
                   instead of per-leaf host-side tree walks)

Scalar operands (clip scale, lr, bias corrections) enter through SMEM;
static hyperparameters (momentum, betas, weight decay) are baked into the
kernel. All accumulation is fp32 regardless of operand dtype; mixed-dtype
operand pairs (bf16 params + fp32 gradient/state buckets) are supported.
Chunks follow kernels.sam_perturb: (8,128)-lane-aligned 1-D blocks, padded;
the reductions view each chunk as (512, 128) lane rows and emit one (8, 128)
tile of partials per chunk, finished outside the kernel.
The jnp oracles live in kernels.ref (tests/test_kernels.py sweeps both).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sam_perturb import (CHUNK, ROW_BLOCK, TILE_BLOCK, _pad_flat,
                                       as_rows, fold_tile, partials_shape)

_VEC = pl.BlockSpec((CHUNK,), lambda i: (i,))
_SCAL = pl.BlockSpec(memory_space=pltpu.SMEM)


def _f32(ref):
    return ref[...].astype(jnp.float32)


# ---------------------------------------------------------------------------
# axpy: out = y + alpha * x
# ---------------------------------------------------------------------------

def _axpy_kernel(scale_ref, x_ref, y_ref, out_ref):
    out_ref[...] = (_f32(y_ref) + scale_ref[0] * _f32(x_ref)).astype(out_ref.dtype)


def fused_axpy(alpha, x_flat: jax.Array, y_flat: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """Single-pass  y + alpha * x  over flat vectors; output dtype = y's."""
    x, n = _pad_flat(x_flat)
    y, _ = _pad_flat(y_flat)
    n_chunks = y.shape[0] // CHUNK
    alpha = jnp.asarray(alpha, jnp.float32).reshape(1)
    out = pl.pallas_call(
        _axpy_kernel,
        grid=(n_chunks,),
        in_specs=[_SCAL, _VEC, _VEC],
        out_specs=_VEC,
        out_shape=jax.ShapeDtypeStruct(y.shape, y_flat.dtype),
        name="bucket_axpy",
        interpret=interpret,
    )(alpha, x, y)
    return out[:n]


# ---------------------------------------------------------------------------
# dot + both squared norms, one pass
# ---------------------------------------------------------------------------

def _dot_norms_kernel(a_ref, b_ref, dot_ref, aa_ref, bb_ref):
    a = _f32(a_ref)
    b = _f32(b_ref)
    dot_ref[...] = fold_tile(a * b)
    aa_ref[...] = fold_tile(a * a)
    bb_ref[...] = fold_tile(b * b)


def fused_dot_norms(a_flat: jax.Array, b_flat: jax.Array, *,
                    interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(<a,b>, ||a||^2, ||b||^2) with fp32 tile partials summed outside."""
    a, _ = _pad_flat(a_flat)
    b, _ = _pad_flat(b_flat)
    n_chunks = a.shape[0] // CHUNK
    part = partials_shape(n_chunks)
    dot, aa, bb = pl.pallas_call(
        _dot_norms_kernel,
        grid=(n_chunks,),
        in_specs=[ROW_BLOCK, ROW_BLOCK],
        out_specs=[TILE_BLOCK, TILE_BLOCK, TILE_BLOCK],
        out_shape=[part, part, part],
        name="bucket_dot_norms",
        interpret=interpret,
    )(as_rows(a), as_rows(b))
    return jnp.sum(dot), jnp.sum(aa), jnp.sum(bb)


# ---------------------------------------------------------------------------
# JOB-delta encoding: amax probe + quantize/shadow/residual in one pass
# ---------------------------------------------------------------------------

def _delta_amax_kernel(p_ref, s_ref, e_ref, out_ref):
    d = _f32(p_ref) - _f32(s_ref) + _f32(e_ref)
    out_ref[...] = fold_tile(jnp.abs(d), jnp.max)


def delta_amax(p_flat: jax.Array, s_flat: jax.Array, e_flat: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """max |p - s + e| (fp32 tile partials, final max outside).

    The scale probe for the int8 JOB-delta encoding: one read pass over the
    params bucket, its shadow, and the error-feedback residual.
    """
    p, _ = _pad_flat(p_flat)     # zero padding is |.|-neutral
    s, _ = _pad_flat(s_flat)
    e, _ = _pad_flat(e_flat)
    n_chunks = p.shape[0] // CHUNK
    partials = pl.pallas_call(
        _delta_amax_kernel,
        grid=(n_chunks,),
        in_specs=[ROW_BLOCK, ROW_BLOCK, ROW_BLOCK],
        out_specs=TILE_BLOCK,
        out_shape=partials_shape(n_chunks),
        name="delta_amax",
        interpret=interpret,
    )(as_rows(p), as_rows(s), as_rows(e))
    return jnp.max(partials)


def _delta_i8_kernel(scale_ref, p_ref, s_ref, e_ref, q_out, s_out, e_out):
    scale = scale_ref[0]
    s = _f32(s_ref)
    d = _f32(p_ref) - s + _f32(e_ref)
    q = jnp.clip(jnp.round(d / scale), -127, 127)
    recon = q * scale
    q_out[...] = q.astype(jnp.int8)
    s_out[...] = s + recon
    e_out[...] = d - recon


def delta_encode_i8(p_flat: jax.Array, s_flat: jax.Array, e_flat: jax.Array,
                    scale, *, interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-pass int8 delta encode: (q, shadow', residual').

    Reads (p, s, e) once and writes the int8 payload plus the advanced fp32
    shadow/residual buckets; `scale` is a traced scalar (SMEM). The oracle is
    ref.delta_encode_i8_flat_jnp; the shadow advance is exactly
    `q.astype(f32) * f32(scale)` so the server's numpy apply reconstructs the
    same fp32 shadow.
    """
    p, n = _pad_flat(p_flat)
    s, _ = _pad_flat(s_flat)
    e, _ = _pad_flat(e_flat)
    n_chunks = p.shape[0] // CHUNK
    scale = jnp.asarray(scale, jnp.float32).reshape(1)
    q, s_new, e_new = pl.pallas_call(
        _delta_i8_kernel,
        grid=(n_chunks,),
        in_specs=[_SCAL, _VEC, _VEC, _VEC],
        out_specs=[_VEC, _VEC, _VEC],
        out_shape=[jax.ShapeDtypeStruct(p.shape, jnp.int8),
                   jax.ShapeDtypeStruct(p.shape, jnp.float32),
                   jax.ShapeDtypeStruct(p.shape, jnp.float32)],
        name="delta_quantize",
        interpret=interpret,
    )(scale, p, s, e)
    return q[:n], s_new[:n], e_new[:n]


# ---------------------------------------------------------------------------
# SGD-family epilogue: clip-wd-momentum-lr-apply in one pass
# ---------------------------------------------------------------------------

def _sgd_kernel(scal_ref, w_ref, g_ref, m_ref, w_out, m_out, *,
                momentum, nesterov, weight_decay):
    w = _f32(w_ref)
    u = _f32(g_ref) * scal_ref[0]
    if weight_decay:
        u = u + weight_decay * w
    m = momentum * _f32(m_ref) + u
    d = momentum * m + u if nesterov else m
    w_out[...] = (w - scal_ref[1] * d).astype(w_out.dtype)
    m_out[...] = m


def _sgd_kernel_nomom(scal_ref, w_ref, g_ref, w_out, *, weight_decay):
    w = _f32(w_ref)
    u = _f32(g_ref) * scal_ref[0]
    if weight_decay:
        u = u + weight_decay * w
    w_out[...] = (w - scal_ref[1] * u).astype(w_out.dtype)


def sgd_epilogue(w_flat: jax.Array, g_flat: jax.Array, m_flat, clip_scale, lr,
                 *, momentum: float = 0.0, nesterov: bool = False,
                 weight_decay: float = 0.0, interpret: bool = False):
    """One-pass SGD tail. Returns (w', m') — m' is None when momentum == 0.

    `clip_scale` and `lr` are traced scalars (SMEM); `momentum`, `nesterov`
    and `weight_decay` are static and baked into the kernel.
    """
    w, n = _pad_flat(w_flat)
    g, _ = _pad_flat(g_flat)
    n_chunks = w.shape[0] // CHUNK
    scal = jnp.stack([jnp.asarray(clip_scale, jnp.float32),
                      jnp.asarray(lr, jnp.float32)])
    if momentum:
        m, _ = _pad_flat(m_flat)
        w_new, m_new = pl.pallas_call(
            functools.partial(_sgd_kernel, momentum=momentum,
                              nesterov=nesterov, weight_decay=weight_decay),
            grid=(n_chunks,),
            in_specs=[_SCAL, _VEC, _VEC, _VEC],
            out_specs=[_VEC, _VEC],
            out_shape=[jax.ShapeDtypeStruct(w.shape, w_flat.dtype),
                       jax.ShapeDtypeStruct(w.shape, jnp.float32)],
            name="sgd_momentum_update",
            interpret=interpret,
        )(scal, w, g, m)
        return w_new[:n], m_new[:n]
    w_new = pl.pallas_call(
        functools.partial(_sgd_kernel_nomom, weight_decay=weight_decay),
        grid=(n_chunks,),
        in_specs=[_SCAL, _VEC, _VEC],
        out_specs=_VEC,
        out_shape=jax.ShapeDtypeStruct(w.shape, w_flat.dtype),
        name="sgd_update",
        interpret=interpret,
    )(scal, w, g)
    return w_new[:n], None


# ---------------------------------------------------------------------------
# AdamW-family epilogue: clip-adam-wd-lr-apply in one pass
# ---------------------------------------------------------------------------

def _adam_kernel(scal_ref, w_ref, g_ref, mu_ref, nu_ref,
                 w_out, mu_out, nu_out, *, b1, b2, eps, weight_decay):
    w = _f32(w_ref)
    g = _f32(g_ref) * scal_ref[0]
    mu = b1 * _f32(mu_ref) + (1.0 - b1) * g
    nu = b2 * _f32(nu_ref) + (1.0 - b2) * g * g
    upd = (mu / scal_ref[2]) / (jnp.sqrt(nu / scal_ref[3]) + eps)
    if weight_decay:
        upd = upd + weight_decay * w
    w_out[...] = (w - scal_ref[1] * upd).astype(w_out.dtype)
    mu_out[...] = mu
    nu_out[...] = nu


def adamw_epilogue(w_flat: jax.Array, g_flat: jax.Array, mu_flat: jax.Array,
                   nu_flat: jax.Array, clip_scale, lr, c1, c2, *,
                   b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                   weight_decay: float = 0.0, interpret: bool = False):
    """One-pass AdamW tail. Returns (w', mu', nu').

    `clip_scale`, `lr` and the bias corrections `c1 = 1-b1^t`, `c2 = 1-b2^t`
    are traced scalars (SMEM); betas/eps/weight_decay are static.
    """
    w, n = _pad_flat(w_flat)
    g, _ = _pad_flat(g_flat)
    mu, _ = _pad_flat(mu_flat)
    nu, _ = _pad_flat(nu_flat)
    n_chunks = w.shape[0] // CHUNK
    scal = jnp.stack([jnp.asarray(clip_scale, jnp.float32),
                      jnp.asarray(lr, jnp.float32),
                      jnp.asarray(c1, jnp.float32),
                      jnp.asarray(c2, jnp.float32)])
    w_new, mu_new, nu_new = pl.pallas_call(
        functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps,
                          weight_decay=weight_decay),
        grid=(n_chunks,),
        in_specs=[_SCAL, _VEC, _VEC, _VEC, _VEC],
        out_specs=[_VEC, _VEC, _VEC],
        out_shape=[jax.ShapeDtypeStruct(w.shape, w_flat.dtype),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)],
        name="adamw_update",
        interpret=interpret,
    )(scal, w, g, mu, nu)
    return w_new[:n], mu_new[:n], nu_new[:n]
