"""SGD, SAM (Foret et al. 21) and Generalized SAM (Zhao et al. 22) baselines.

These are the synchronous references AsyncSAM is compared against in paper
Tables 4.1/4.2 and Figures 3/4. They share the framework step protocol defined
in repro.core.api.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.perturb import (gradient_norm_penalty_direction,
                                perturb as _perturb, perturb_masked as _perturb_masked)
from repro.core.api import (LossFn, Method, MethodConfig, TrainState, _finish,
                            step_rng, value_and_grad_acc)
from repro.core.ascent import split_batch
from repro.optim import GradientTransform
from repro.utils import trees


def make_sgd(cfg: MethodConfig) -> Method:
    def init(params, rng):
        return ()

    def make_step(loss_fn: LossFn, optimizer: GradientTransform):
        vg = value_and_grad_acc(loss_fn, cfg.n_microbatches)

        def step(state: TrainState, batch):
            batch, _ = split_batch(batch)
            rng = step_rng(state)
            with jax.named_scope("descent"):
                (loss, aux), grads = vg(state.params, batch, rng)
            return _finish(state, optimizer, grads, (), {"loss": loss, **_m(aux)},
                           guard=cfg.guard_update)

        return step

    return Method("sgd", init, make_step)


def make_sam(cfg: MethodConfig) -> Method:
    """Vanilla SAM: two sequential gradient evaluations per step (Eq. 1)."""

    def init(params, rng):
        return ()

    def make_step(loss_fn: LossFn, optimizer: GradientTransform):
        vg = value_and_grad_acc(loss_fn, cfg.n_microbatches)

        def step(state: TrainState, batch):
            batch, ascent_batch = split_batch(batch)
            if cfg.same_batch_ascent or ascent_batch is None:
                ascent_batch = batch
            rng = step_rng(state)
            # --- gradient ascent (perturbation) ---
            with jax.named_scope("ascent"):
                (loss_w, _), g_ascent = vg(state.params, ascent_batch, rng)
            with jax.named_scope("perturb"):
                w_hat = _perturb(state.params, g_ascent, cfg.rho,
                                 fused=cfg.fused_update)
            # --- gradient descent at the perturbed point ---
            with jax.named_scope("descent"):
                (loss, aux), grads = vg(w_hat, batch, rng)
            metrics = {"loss": loss, "loss_at_w": loss_w,
                       "ascent_norm": trees.global_norm(g_ascent), **_m(aux)}
            return _finish(state, optimizer, grads, (), metrics,
                           guard=cfg.guard_update)

        return step

    return Method("sam", init, make_step)


def make_gsam(cfg: MethodConfig) -> Method:
    """Generalized SAM / gradient-norm penalty: mix ∇L(w) and ∇L(ŵ) by alpha."""

    def init(params, rng):
        return ()

    def make_step(loss_fn: LossFn, optimizer: GradientTransform):
        vg = value_and_grad_acc(loss_fn, cfg.n_microbatches)

        def step(state: TrainState, batch):
            batch, ascent_batch = split_batch(batch)
            if cfg.same_batch_ascent or ascent_batch is None:
                ascent_batch = batch
            rng = step_rng(state)
            (loss_w, _), g_w = vg(state.params, ascent_batch, rng)
            w_hat = _perturb(state.params, g_w, cfg.rho, fused=cfg.fused_update)
            (loss, aux), g_hat = vg(w_hat, batch, rng)
            grads = gradient_norm_penalty_direction(g_w, g_hat, cfg.alpha)
            metrics = {"loss": loss, "loss_at_w": loss_w, **_m(aux)}
            return _finish(state, optimizer, grads, (), metrics,
                           guard=cfg.guard_update)

        return step

    return Method("gsam", init, make_step)


def _m(aux: dict) -> dict:
    """Pass through scalar aux metrics only."""
    return {k: v for k, v in aux.items()
            if isinstance(v, jax.Array) and v.ndim == 0}
