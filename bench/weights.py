"""Weights and keys from `--seed`, made by the benchmark and not the program.

`make_params` runs an architecture's `init_params` (`bench/archs/<arch>.py`)
inside one jitted call on the device, placed by the shardings it is given.
The program is handed these weights; the reference regenerates the same tree
from the same seed, on its own placement, so it never takes a weight the
program holds.
"""
from __future__ import annotations

from types import ModuleType

import jax


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed; `PRNGKey` keeps only 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make_params(seed: int, arch: ModuleType, dims: dict,
                out_shardings=None) -> dict:
    """`arch.init_params` from `seed` in one jitted call, placed by
    `out_shardings` (a pytree of shardings, or None for the default
    device)."""
    fn = jax.jit(lambda k: arch.init_params(k, dims),
                 out_shardings=out_shardings)
    return fn(seed_key(seed))
