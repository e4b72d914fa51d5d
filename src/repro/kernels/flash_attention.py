"""Pallas TPU flash attention forward (causal / sliding-window / GQA).

Design (TPU v5e target):
* layout (B*H, S, hd) inside the kernel — contiguous (S, hd) tiles feed the
  MXU directly; the public wrapper transposes from the model's (B, S, H, hd);
* grid (B*H, q_blocks, kv_blocks) with the kv axis innermost and sequential
  ("arbitrary"), carrying the online-softmax state in VMEM scratch across kv
  steps: the running max and sum as (block_q, 128) rows whose lanes all hold
  the same value, and an fp32 (block_q, hd_v) accumulator;
* tiles are chosen from the shape by `block_sizes`: the largest of 512, 256
  and 128 that divides the sequence and, under a sliding window, is no wider
  than the window (a sequence shorter than 128 is one block; explicit
  `block_q`/`block_k` win);
* a kv block is fully masked for a query block when it lies wholly after it
  (causal) or wholly before its window. Such a step computes nothing, and the
  k/v index_map clamps its block index to the last (causal) or first (window)
  visible block, which is the block already resident, so it issues no copy;
* only blocks that straddle the diagonal or the window edge build and apply
  the element mask; fully visible blocks run the unmasked body;
* precision: q k^T is one MXU product on the operands in their own dtype
  with fp32 accumulation, scaled by 1/sqrt(hd) in fp32; the softmax
  statistics are fp32; p v rounds p to v's dtype (bf16 in a bf16 model, like
  XLA's default-precision einsum) and accumulates in fp32; fp32 inputs stay
  fp32 end to end;
* GQA folds the kv-head index in the k/v index_map (no materialized repeat).

Validated against repro.kernels.ref.mha_reference in interpret mode
(tests/test_kernels.py sweeps shapes, dtypes and the chosen tiles).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128
_BLOCKS = (512, 256, 128)     # tile candidates, largest first


def block_sizes(sq: int, sk: int, window: Optional[int]) -> tuple[int, int]:
    """(block_q, block_k) for queries of length `sq` over `sk` keys.

    Each is the largest candidate that divides its sequence and, under a
    sliding `window`, is no wider than the window (but at least 128), so
    that blocks wholly outside it are skipped. A sequence shorter than 128
    is one block; one that no candidate divides gets 128, which
    `flash_attention` refuses. On a v5e at [32, 2048, 128] causal bf16,
    512 x 512 was the fastest pair of 128-1024 (PERF.md). A 512 x 512 step
    at hd 256 in fp32 holds about 12 MiB of VMEM, within a v5e's 16 MiB.
    """
    cap = _BLOCKS[0] if window is None else max(window, _BLOCKS[-1])

    def pick(s):
        return next((c for c in _BLOCKS if c <= cap and s % c == 0),
                    min(_BLOCKS[-1], s))

    return pick(sq), pick(sk)


def _lanes_to(x: jax.Array, n: int) -> jax.Array:
    """(rows, 128) with equal lanes -> (rows, n)."""
    if n > _LANES:
        x = jnp.tile(x, (1, -(-n // _LANES)))
    return x[:, :n]


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               block_q: int, block_k: int, sm_scale: float,
               causal: bool, window: Optional[int]):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def update(masked: bool):
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                     # (bq, bk) fp32
        if masked:
            # query position minus key position
            d = (q_start - k_start
                 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                 - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            keep = True
            if causal:
                keep &= d >= 0
            if window is not None:
                keep &= d < window
            s = jnp.where(keep, s, _NEG_INF)

        m_prev = m_ref[...]                                  # (bq, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes_to(m_new, block_k))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0]
        acc_ref[...] = (acc_ref[...] * _lanes_to(alpha, v.shape[-1])
                        + jax.lax.dot(p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))

    if causal or window is not None:
        q_end = q_start + block_q - 1
        k_end = k_start + block_k - 1
        visible = full = True
        if causal:
            visible &= k_start <= q_end
            full &= k_end <= q_start
        if window is not None:
            visible &= q_start - k_end < window
            full &= q_end - k_start < window

        @pl.when(full)
        def _():
            update(masked=False)

        @pl.when(visible & jnp.logical_not(full))
        def _():
            update(masked=True)
    else:
        update(masked=False)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / _lanes_to(l, acc_ref.shape[-1])
                    ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """q (B,Sq,H,hd); k (B,Sk,K,hd), v (B,Sk,K,hd_v) with K | H.
    Returns (B,Sq,H,hd_v). Tiles default to `block_sizes`."""
    b, sq, h, hd = q.shape
    sk, n_kv, hd_v = k.shape[1], k.shape[2], v.shape[-1]
    assert h % n_kv == 0
    auto_q, auto_k = block_sizes(sq, sk, window)
    block_q = min(block_q or auto_q, sq)
    block_k = min(block_k or auto_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, block_q, sk, block_k)

    qt = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, hd)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * n_kv, sk, hd)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * n_kv, sk, hd_v)
    group = h // n_kv

    def kv_index(bh, qi, ki):
        # a fully masked step names the visible block already resident
        if causal:
            ki = jnp.minimum(ki, (qi * block_q + block_q - 1) // block_k)
        if window is not None:
            ki = jnp.maximum(
                ki, jnp.maximum(qi * block_q - window + 1, 0) // block_k)
        return bh // group, ki, 0

    kernel = functools.partial(
        _fa_kernel, block_q=block_q, block_k=block_k,
        sm_scale=1.0 / math.sqrt(hd), causal=causal, window=window)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd_v), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd_v),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max m
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, hd_v), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(qt, kt, vt)

    return jnp.moveaxis(out.reshape(b, h, sq, hd_v), 1, 2)
