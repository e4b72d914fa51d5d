"""Persistent XLA compile cache at a fixed place in the checkout.

Called by entry points (`launch/train.py main()`, `chip_smoke.py`) before
their first compile, never at import: a full-width training step takes tens
of seconds to compile, and a fresh process on the same checkout can read it
back instead.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_checkout_compile_cache() -> str:
    """Point JAX's persistent compile cache at `<checkout>/.jax_cache`.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
    sets nothing. The path is fixed (no pid, time or temporary name) because
    it is part of the cache key: a moving directory never hits. Returns the
    directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
