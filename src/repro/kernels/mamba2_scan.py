"""Pallas TPU chunked SSD (Mamba2) sequence-mixing kernel.

Blocking (zamba2: P=64, N=64, chunk T=128 — MXU-aligned):
* grid (B, H, n_chunks); the chunk axis is innermost and sequential
  ("arbitrary"), carrying the (P, N) state in fp32 VMEM scratch;
* per step the kernel loads x as (T,P) and (P,T), dt as (T,1) and (1,T),
  and b/c (T,N) tiles (both orientations come from HBM because Mosaic
  transposes poorly and has no cumsum), and computes
    decay:        seg = tril(1) (dt a ⊙ strict-tril)        1 MXU matmul
    intra-chunk:  y  = (tril(C Bᵀ) ⊙ exp(seg)) (dt ⊙ x)     2 MXU matmuls
    state in/out: y += (exp(cum) ⊙ C) h_inᵀ ;  h_out = exp(total) h_in + ...
  entirely in VMEM; only y (T,P) returns to HBM per step.

The jnp mirror (ref.mamba2_chunked_jnp) is the oracle; decode steps use the
sequential reference (single token, no kernel needed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def _ssd_kernel(x_ref, xt_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, d_ref,
                y_ref, h_out_ref, h_ref, *, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)       # (T, P)
    xt = xt_ref[0, 0, 0].astype(jnp.float32)     # (P, T)  x transposed
    dt_c = dtc_ref[0, 0, 0].astype(jnp.float32)  # (T, 1)
    dt_r = dtr_ref[0, 0, 0].astype(jnp.float32)  # (1, T)
    b = b_ref[0, 0, 0].astype(jnp.float32)       # (T, N)
    c = c_ref[0, 0, 0].astype(jnp.float32)       # (T, N)
    a = a_ref[hi]                                # scalar decay rate (<0), SMEM
    d = d_ref[hi]                                # scalar skip, SMEM

    # Mosaic has no cumsum: the decay exponents come from one MXU matmul,
    #   seg[t, s] = sum_{s < r <= t} dt[r] a  = cum[t] - cum[s]   (s <= t)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col
    seg = _dot(tri.astype(jnp.float32),
               jnp.where(row > col, dt_c * a, 0.0))           # (T, T)
    cum_c = seg[:, 0:1] + dt_c[0:1, :] * a                     # (T, 1) inclusive
    to_end = seg[chunk - 1:chunk, :]                           # (1, T) total - cum
    total = jnp.sum(dt_c) * a                                  # scalar

    # intra-chunk: y = (tril(C Bᵀ) ⊙ exp(seg)) (dt ⊙ x)
    gmat = jnp.where(tri, jnp.exp(seg), 0.0)
    xdt = xt * dt_r                                            # (P, T)
    y = _dot(_dot(c, b, _NT) * gmat, xdt, _NT)                 # (T, P)

    # contribution of the entering state
    h_in = h_ref[...]                                          # (P, N)
    y += jnp.exp(cum_c) * _dot(c, h_in, _NT)                   # (T, P)

    # next chunk state: h = exp(total) h_in + (dt x exp(total - cum))ᵀ B
    h_ref[...] = (jnp.exp(jnp.full(h_in.shape, total)) * h_in
                  + _dot(xdt * jnp.exp(to_end), b))

    y_ref[0, 0, 0] = (y + d * x).astype(y_ref.dtype)
    h_out_ref[0, 0] = h_ref[...]   # revisited each chunk; final chunk wins


def mamba2_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, d: jax.Array, *, chunk: int = 128,
                   interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """x (B,S,H,P); dt (B,S,H); a,d (H,); b,c (B,S,G,N). Returns (y, h_final).

    Grid semantics match ref.mamba2_chunked_jnp (G groups broadcast onto H).
    The scan starts from a zero state; a carried state (serving) takes the
    jnp path in `kernels.ops`.
    """
    from repro.kernels import ref

    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    if S % chunk != 0:
        return ref.mamba2_chunked_jnp(x, dt, a, b, c, d, chunk=chunk)
    nc = S // chunk
    rep = H // G
    # (B,S,H,*) -> (B,H,nc,T,*) tiles; x and dt also as (...,*,T) rows,
    # since the kernel needs both orientations and Mosaic transposes poorly
    xh = jnp.moveaxis(x, 2, 1).reshape(B, H, nc, chunk, P)
    dth = jnp.moveaxis(dt, 2, 1).reshape(B, H, nc, chunk, 1)
    bh = jnp.repeat(jnp.moveaxis(b, 2, 1), rep, axis=1).reshape(B, H, nc, chunk, N)
    ch = jnp.repeat(jnp.moveaxis(c, 2, 1), rep, axis=1).reshape(B, H, nc, chunk, N)

    def tile(shape):
        return pl.BlockSpec((1, 1, 1) + shape,
                            lambda bi, hi, ci: (bi, hi, ci, 0, 0))

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            tile((chunk, P)),
            tile((P, chunk)),
            tile((chunk, 1)),
            tile((1, chunk)),
            pl.BlockSpec(memory_space=pltpu.SMEM),      # a (H,), per-head scalar
            tile((chunk, N)),
            tile((chunk, N)),
            pl.BlockSpec(memory_space=pltpu.SMEM),      # d (H,), per-head scalar
        ],
        out_specs=[
            tile((chunk, P)),
            pl.BlockSpec((1, 1, P, N), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="mamba_chunk_scan",
        interpret=interpret,
    )(xh, jnp.swapaxes(xh, -1, -2), dth, jnp.swapaxes(dth, -1, -2),
      a.astype(jnp.float32), bh, ch, d.astype(jnp.float32))

    y = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)
    return y, h_final
