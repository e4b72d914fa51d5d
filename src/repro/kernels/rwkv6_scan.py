"""Pallas TPU kernel for the RWKV6 ("Finch") wkv recurrence.

The recurrence  S_t = diag(exp(w_t)) S_{t-1} + k_t v_tᵀ ;  y_t = r_t·(S_{t-1}
+ u∘k_t ⊗ v_t)  is sequential in t with per-channel data-dependent decay, so
the MXU-friendly "chunked matmul" form needs exp(-cum) rescaling that
overflows fp32 for realistic decay magnitudes. This kernel instead keeps the
(K, V) state resident in VMEM and walks the sequence in chunks:

* grid (B, H, n_chunks), chunk axis sequential, the state fp32 in scratch,
  held transposed as (V, K) so that the per-channel decay exp(w_t) scales
  it as a row broadcast (Mosaic transposes poorly);
* per chunk, r/k/v/w (T,K|V) tiles are loaded once from HBM; each of the T
  inner steps reads one row per operand (fp32 tiles: Mosaic loads and stores
  single rows at any offset only for 32-bit data) and makes a rank-1 update
  of the VMEM state — HBM traffic is O(S·K) instead of O(S·K·V) for a naive
  per-token implementation.

Oracle: ref.rwkv6_scan_ref (tests sweep shapes/dtypes in interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref, s_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)              # (1, K)
    hp = jax.lax.Precision.HIGHEST

    def row(ref, t):
        return ref[0, 0, 0, pl.ds(t, 1), :].astype(jnp.float32)

    def step(t, st):                              # st: state transposed (V, K)
        rt, kt, vt, wt = row(r_ref, t), row(k_ref, t), row(v_ref, t), row(w_ref, t)
        kv_t = jax.lax.dot_general(vt, kt, (((0,), (0,)), ((), ())),
                                   precision=hp)  # (V, K) rank-1
        yt = jax.lax.dot_general(rt, st, (((1,), (1,)), ((), ())),
                                 precision=hp)    # (1, V)
        yt = yt + jnp.sum(rt * u * kt) * vt
        y_ref[0, 0, 0, pl.ds(t, 1), :] = yt.astype(y_ref.dtype)
        return jnp.exp(wt) * st + kv_t

    st = jax.lax.fori_loop(0, chunk, step, s_ref[...])
    s_ref[...] = st
    s_out_ref[0, 0] = st                          # final chunk's write wins


def rwkv6_chunked(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                  u: jax.Array, *, chunk: int = 64,
                  interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """r,k,w (B,S,H,K); v (B,S,H,V); u (H,K). Returns (y (B,S,H,V), state).

    The scan starts from a zero state; a carried state (serving) takes the
    jnp path in `kernels.ops`.
    """
    from repro.kernels import ref

    B, S, H, K = r.shape
    V = v.shape[-1]
    if S % chunk != 0:
        return ref.rwkv6_scan_ref(r, k, v, w, u)
    nc = S // chunk

    def tile(x, d):
        x = jnp.moveaxis(x, 2, 1).reshape(B, H, nc, chunk, d)
        return x.astype(jnp.float32)

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    y, s_final = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, K), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, K), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, V), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, K), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, K), lambda bi, hi, ci: (hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, V), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, V, K), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, chunk, V), jnp.float32),
            jax.ShapeDtypeStruct((B, H, V, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((V, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="rwkv_chunk_scan",
        interpret=interpret,
    )(tile(r, K), tile(k, K), tile(v, V), tile(w, K), u.reshape(H, 1, K))

    y = jnp.moveaxis(y.reshape(B, H, S, V), 1, 2).astype(r.dtype)
    return y, jnp.swapaxes(s_final, -1, -2)
