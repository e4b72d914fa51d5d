"""Weights and keys from `--seed`, made by the benchmark and not the program.

`init_params` builds the decoder's parameter tree in the layout the program
trains (stacked layers under "blocks", tied embedding under "embedding") with
plain jax.random calls, inside one jitted call on the device, in float32 as
the configuration trains them. The reference regenerates the same tree from
the same seed, so it never takes a weight the program holds.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed; `PRNGKey` keeps only 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def init_params(key: jax.Array, dims: dict) -> dict:
    """Fan-in scaled normal weights; `dims` is a configuration's "model"."""
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


def make_params(seed: int, dims: dict, out_shardings=None) -> dict:
    """`init_params` from `seed` in one jitted call, placed by
    `out_shardings` (a pytree of shardings, or None for the default
    device)."""
    fn = jax.jit(lambda k: init_params(k, dims), out_shardings=out_shardings)
    return fn(seed_key(seed))

