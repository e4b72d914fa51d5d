"""Pallas TPU kernels for the SAM perturbation:  w + rho * g / ||g||.

At pod scale the perturbation touches every parameter element twice per step
(read w, read g, write w_hat) on top of the optimizer update. Fusing the
norm-scale-axpy into two single-pass kernels halves the HBM traffic of the
perturb path versus the unfused jnp composition (norm reduce + scalar bcast +
mul + add each re-streaming the tensors):

  kernel 1 (sq_norm): grid over chunks viewed as (512, 128) lane rows, an
      (8, 128) fp32 partial sum-of-squares per chunk (VPU adds only, no
      cross-lane reduce), final scalar sum outside (one tiny reduce);
  kernel 2 (perturb): grid over the same chunks, out = w + (rho/sqrt(n)) * g,
      with the precomputed scale entering through SMEM.

Chunks are (8, 128)-lane aligned. The jnp oracle is ref.sam_perturb_flat_jnp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64 * 1024  # fp32 elements per grid step: 256 KiB VMEM per operand
LANES = 128
ROWS = CHUNK // LANES
# Reductions emit one (8, 128) tile of partials per chunk: Mosaic refuses
# rank-1 blocks that are not a multiple of 128 elements, so a (1,) scalar
# partial per grid step does not lower on the chip.
TILE = (8, LANES)
ROW_BLOCK = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
TILE_BLOCK = pl.BlockSpec(TILE, lambda i: (i, 0))


def _pad_flat(x: jax.Array) -> tuple[jax.Array, int]:
    n = x.shape[0]
    padded = (n + CHUNK - 1) // CHUNK * CHUNK
    if padded != n:
        x = jnp.pad(x, (0, padded - n))
    return x, n


def as_rows(x: jax.Array) -> jax.Array:
    """Padded flat vector -> (n / 128, 128) lane rows (a free reshape)."""
    return x.reshape(-1, LANES)


def partials_shape(n_chunks: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((n_chunks * TILE[0], LANES), jnp.float32)


def fold_tile(x: jax.Array, op=jnp.sum) -> jax.Array:
    """(ROWS, 128) -> (8, 128): reduce whole vreg tiles elementwise."""
    return op(x.reshape(ROWS // TILE[0], *TILE), axis=0)


def _sq_norm_kernel(g_ref, out_ref):
    g = g_ref[...].astype(jnp.float32)
    out_ref[...] = fold_tile(g * g)


def sq_norm(g_flat: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Sum of squares of a flat vector (tile partials, summed outside)."""
    g, _ = _pad_flat(g_flat)
    n_chunks = g.shape[0] // CHUNK
    partials = pl.pallas_call(
        _sq_norm_kernel,
        grid=(n_chunks,),
        in_specs=[ROW_BLOCK],
        out_specs=TILE_BLOCK,
        out_shape=partials_shape(n_chunks),
        name="bucket_sq_norm",
        interpret=interpret,
    )(as_rows(g))
    return jnp.sum(partials)


def _perturb_kernel(scale_ref, w_ref, g_ref, out_ref):
    scale = scale_ref[0]
    out_ref[...] = (w_ref[...].astype(jnp.float32)
                    + scale * g_ref[...].astype(jnp.float32)
                    ).astype(out_ref.dtype)


def sam_perturb(w_flat: jax.Array, g_flat: jax.Array, rho, sq_norm_val, *,
                interpret: bool = False) -> jax.Array:
    """Fused w + rho * g / sqrt(sq_norm) over flat vectors (single HBM pass)."""
    w, n = _pad_flat(w_flat)
    g, _ = _pad_flat(g_flat)
    n_chunks = w.shape[0] // CHUNK
    scale = (jnp.asarray(rho, jnp.float32)
             / (jnp.sqrt(jnp.asarray(sq_norm_val, jnp.float32)) + 1e-12))
    out = pl.pallas_call(
        _perturb_kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # scalar scale
            pl.BlockSpec((CHUNK,), lambda i: (i,)),
            pl.BlockSpec((CHUNK,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((CHUNK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(w.shape, w_flat.dtype),
        name="sam_perturb",
        interpret=interpret,
    )(scale.reshape(1), w, g)
    return out[:n]
