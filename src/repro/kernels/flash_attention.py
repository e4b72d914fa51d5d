"""Pallas TPU flash attention (causal / sliding-window / GQA).

Design (TPU v5e target):
* layout (B, H, S, hd) inside the kernel — contiguous (S, hd) tiles feed the
  MXU directly; the public wrapper transposes from the model's (B, S, H, hd);
* grid (B*H, q_blocks, kv_blocks) with the kv axis innermost and sequential
  ("arbitrary"), carrying the online-softmax state (m, l, acc) in VMEM scratch
  across kv steps;
* BlockSpec tiles: q (block_q, hd), k/v (block_k, hd) — hd is 64...256 for
  every assigned arch, so tiles are (128, 128)-aligned for the MXU with fp32
  accumulation in scratch;
* causal + sliding-window masking via block-level early-out: fully-masked kv
  blocks write nothing and fully-visible blocks skip the mask computation;
* GQA folds the kv-head index in the k/v index_map (no materialized repeat).

Validated against repro.kernels.ref.mha_reference in interpret mode
(tests/test_kernels.py sweeps shapes and dtypes).
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


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               block_q: int, block_k: int, sm_scale: float,
               causal: bool, window: Optional[int], kv_len: int):
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

    def compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale         # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        scale = jnp.exp(m_prev - m_new)
        l_new = l_prev * scale + jnp.sum(p, axis=-1)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = (acc_ref[...] * scale[:, None]
                        + jax.lax.dot(p.astype(v.dtype), v))
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal or window is not None:
        # block-level visibility: skip fully-masked kv blocks
        visible = jnp.asarray(True)
        if causal:
            visible &= k_start <= q_start + block_q - 1
        if window is not None:
            visible &= q_start - (k_start + block_k - 1) < window

        @pl.when(visible)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q (B,Sq,H,hd); k/v (B,Sk,K,hd) with K | H. Returns (B,Sq,H,hd)."""
    b, sq, h, hd = q.shape
    sk, n_kv = k.shape[1], k.shape[2]
    assert h % n_kv == 0
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, block_q, sk, block_k)

    qt = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, hd)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * n_kv, sk, hd)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * n_kv, sk, hd)
    group = h // n_kv

    grid = (b * h, sq // block_q, sk // block_k)

    kernel = functools.partial(
        _fa_kernel, block_q=block_q, block_k=block_k,
        sm_scale=1.0 / math.sqrt(hd), causal=causal, window=window, kv_len=sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki: (bh // group, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki: (bh // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # running max m
            pltpu.VMEM((block_q,), jnp.float32),       # running sum l
            pltpu.VMEM((block_q, hd), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(qt, kt, vt)

    return jnp.moveaxis(out.reshape(b, h, sq, hd), 1, 2)
