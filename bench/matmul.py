"""Matrix products for the plain references in `bench/archs/`.

`fp32` is every product in float32 at HIGHEST precision. `fp8` is the
control: each product's operands rounded to float8 (e4m3 forward, e5m2
cotangents, one scale per tensor), the step below the bfloat16 the
configurations compute in.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


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
