"""Plain reference of a training cell's method step, over any architecture.

The method's step around an architecture's `loss` (`bench/archs/<arch>.py`):
`sgd` (the plain gradient) or `async_sam` (descent gradient at
w + rho * a / ||a|| with the ascent gradient a of the previous step, taken on
a separate ascent batch at the unperturbed weights; no perturbation on the
first step), then AdamW with a global-norm clip. Straight `jax.numpy` in
float32; the precision of the matrix products is the loss's (`"fp32"`, or
the `"fp8"` control). Imports nothing of the program.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree.leaves(tree)))


class State(NamedTuple):
    params: dict
    mu: dict
    nu: dict
    count: jax.Array
    ascent: dict           # async_sam: the previous step's ascent gradient
    have_ascent: jax.Array


def init_state(params: dict) -> State:
    def zeros():
        return jax.tree.map(jnp.zeros_like, params)

    return State(params, zeros(), zeros(), jnp.zeros((), jnp.int32), zeros(),
                 jnp.zeros((), bool))


def make_step(loss: Callable, dims: dict, train: dict,
              precision: str = "fp32"):
    """(state, batch) -> (state, loss, clipped gradient) for one step of
    `train` ({"method", "rho", "lr", "clip_norm", "weight_decay", "b1",
    "b2", "eps"}) over an architecture's `loss(params, batch, dims,
    precision)`."""
    lossf = functools.partial(loss, dims=dims, precision=precision)
    vg = jax.value_and_grad(lossf)

    def step(st: State, batch: dict):
        descent = {k: batch[k] for k in ("tokens", "labels")}
        if train["method"] == "async_sam":
            rho = jnp.where(st.have_ascent, train["rho"], 0.0)
            scale = rho / (global_norm(st.ascent) + 1e-12)
            w = jax.tree.map(lambda p, a: p + scale * a, st.params, st.ascent)
            value, g = vg(w, descent)
            ascent = jax.grad(lossf)(st.params, batch["ascent"])
            have = jnp.ones((), bool)
        elif train["method"] == "sgd":
            value, g = vg(st.params, descent)
            ascent, have = st.ascent, st.have_ascent
        else:
            raise ValueError(f"no reference for method {train['method']!r}")
        gn = global_norm(g)
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, train["clip_norm"] / (gn + 1e-12)),
            g)
        b1, b2 = train["b1"], train["b2"]
        count = st.count + 1
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, st.mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, st.nu, g)
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, m, v: p - train["lr"] * (
                (m / c1) / (jnp.sqrt(v / c2) + train["eps"])
                + train["weight_decay"] * p),
            st.params, mu, nu)
        return State(params, mu, nu, count, ascent, have), value, g

    return step
