"""Training-method API: a uniform step interface over the SAM family.

Every method (SGD, SAM, AsyncSAM, GSAM, LookSAM, ESAM, AE-SAM, MESA) is exposed
as a `Method` with

    init(params, rng)                  -> method_state pytree
    step(state, batch)                 -> (state, metrics)     [built by make_step]

where `state` is the framework-wide `TrainState`. The step functions are pure
and jit/pjit-friendly: under pjit with sharded batches the mini-batch mean loss
autodiffs to globally-reduced gradients, so the same code runs on 1 CPU device
and on the 512-chip production mesh.

The loss callback protocol is

    loss_fn(params, batch, rng) -> (scalar_loss, aux_dict)

aux may contain "logits" (used by MESA's trajectory loss) and arbitrary
metrics that are passed through to the step metrics.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.optim import GradientTransform, apply_updates
from repro.optim.fused import fused_apply
from repro.utils import buckets, trees

Pytree = Any
LossFn = Callable[[Pytree, Any, jax.Array], tuple[jax.Array, dict]]


class TrainState(NamedTuple):
    step: jax.Array          # int32 scalar
    rng: jax.Array           # PRNG key threaded through data-order-independent noise
    params: Pytree
    opt_state: Pytree
    method_state: Pytree     # method-specific carry (e.g. AsyncSAM's a_{t-1})


@dataclasses.dataclass(frozen=True)
class MethodConfig:
    """One config object for the whole family; irrelevant fields are ignored.

    name: sgd | sam | async_sam | gsam | looksam | esam | aesam | mesa
    rho: perturbation radius r (paper Table A.2 uses 0.05~0.1).
    ascent_fraction: b'/b for AsyncSAM (paper: {25,50,75,100}%).
    same_batch_ascent: SAM convention — ascent uses the same minibatch as
        descent (Foret et al.); AsyncSAM uses *different* samples by design.
    alpha: GSAM mixing coefficient (0.7~0.9).
    looksam_k: gradient-ascent reuse interval (paper fixes 2).
    esam_beta: fraction of parameters perturbed by ESAM's SWP.
    aesam_lambda_hi: z-score threshold above which AE-SAM takes a SAM step.
    mesa_decay / mesa_lambda / mesa_temp / mesa_start_step: MESA EMA-distill.
    compressor / topk_fraction: lossy ascent-exchange compression (DESIGN §2).
    """
    name: str = "async_sam"
    rho: float = 0.1
    ascent_fraction: float = 0.25
    same_batch_ascent: bool = True
    alpha: float = 0.8
    looksam_k: int = 2
    esam_beta: float = 0.6
    aesam_lambda_hi: float = 1.0
    aesam_ema: float = 0.9
    mesa_decay: float = 0.995
    mesa_lambda: float = 0.8
    mesa_temp: float = 1.5
    mesa_start_step: int = 200
    compressor: str = "none"
    topk_fraction: float = 0.01
    n_microbatches: int = 1   # gradient accumulation (activation-memory lever)
    ascent_interval: int = 1  # refresh a_t every k steps (beyond-paper; tau<=k)
    # In-step numerics guard (runtime.guard): a non-finite loss or gradient
    # discards the whole update by tree-select inside the jitted step
    # (params/opt_state/method_state carried unchanged, step/rng advance so
    # the batch is consumed), and the step emits update_skipped /
    # nonfinite_count. Honored by sgd, sam, gsam and async_sam — the methods
    # the guard ladder drives; the long-tail variants ignore it.
    guard_update: bool = False
    # Flat-buffer fused weight-space path (perturb axpy, ascent-refresh
    # dot/norms). None defers to the platform default: on for TPU, off
    # elsewhere (utils.buckets.fused_path_enabled). Executors resolve and pin
    # this; the matching optimizer-epilogue switch lives on FusedSpec.enabled.
    fused_update: Optional[bool] = None


@dataclasses.dataclass(frozen=True)
class Method:
    """A named pair of (state init, step builder).

    `cfg` is the MethodConfig the factory closed over (attached by
    `core.make_method`); executors use it to rebuild the method with a
    resolved `fused_update` flag. None for hand-constructed Methods.
    """
    name: str
    init: Callable[[Pytree, jax.Array], Pytree]
    make_step: Callable[[LossFn, GradientTransform], Callable]
    cfg: Optional[MethodConfig] = None


def init_train_state(params: Pytree, optimizer: GradientTransform,
                     method: Method, rng: jax.Array) -> TrainState:
    init_rng, state_rng = jax.random.split(rng)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        rng=state_rng,
        params=params,
        opt_state=optimizer.init(params),
        method_state=method.init(params, init_rng),
    )


def _finish(state: TrainState, optimizer: GradientTransform, grads: Pytree,
            method_state: Pytree, metrics: dict, *,
            guard: bool = False) -> tuple[TrainState, dict]:
    """Shared tail: inner-optimizer update + state threading.

    Canonical sgd/adamw chains take the fused flat-buffer path when enabled
    (optim.fused): one single-pass kernel per dtype bucket instead of the
    per-leaf update + apply_updates passes, with identical opt_state layout.

    guard=True (MethodConfig.guard_update) adds the in-step numerics check:
    a non-finite loss or global gradient norm discards the update — params /
    opt_state / method_state are tree-selected back to their previous values
    INSIDE the jit (a post-hoc host-side skip is impossible: executors donate
    the input state buffers), while step and rng still advance so the
    anomalous batch is consumed, not replayed. The step then carries
    `update_skipped` (1.0 on a skip) and `nonfinite_count` (non-finite
    gradient elements) for the host-side guard ladder (runtime.guard).
    """
    with jax.named_scope("update"):
        metrics = dict(metrics)
        fused = fused_apply(optimizer, grads, state.opt_state, state.params)
        if fused is not None:
            params, opt_state, gnorm = fused
            metrics.setdefault("grad_norm", gnorm)
        else:
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = apply_updates(state.params, updates)
            metrics.setdefault("grad_norm", trees.global_norm(grads))
        if guard:
            # a single non-finite element makes the global norm non-finite, so
            # the ok verdict needs no extra pass; the element count is one more
            # reduction over grads, paid only when the guard is on
            ok = (jnp.isfinite(metrics["grad_norm"])
                  & jnp.isfinite(metrics.get("loss", jnp.float32(0.0))))
            keep = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
            params = jax.tree.map(keep, params, state.params)
            opt_state = jax.tree.map(keep, opt_state, state.opt_state)
            method_state = jax.tree.map(keep, method_state, state.method_state)
            nonfinite = sum(jnp.sum(~jnp.isfinite(g)).astype(jnp.int32)
                            for g in jax.tree.leaves(grads))
            metrics["update_skipped"] = (~ok).astype(jnp.float32)
            metrics["nonfinite_count"] = jnp.asarray(nonfinite, jnp.float32)
        rng, _ = jax.random.split(state.rng)
        new_state = TrainState(step=state.step + 1, rng=rng, params=params,
                               opt_state=opt_state, method_state=method_state)
        return new_state, metrics


def step_rng(state: TrainState) -> jax.Array:
    """Per-step PRNG derived from (rng, step): restart-stable."""
    return jax.random.fold_in(state.rng, state.step)


def view_loss(loss_fn: LossFn) -> LossFn:
    """Make a loss callback accept bucket-resident parameters.

    When params arrive as a `buckets.BucketedState`, the model sees the
    zero-copy pytree view; differentiating through the view transposes to
    cotangent accumulation straight into the buffers, so `jax.grad` of the
    wrapped loss returns gradients already bucket-shaped — no gather pass
    between autodiff and the fused weight-space kernels. Plain pytrees pass
    through untouched.
    """
    def fn(params, batch, rng):
        return loss_fn(buckets.tree_view(params), batch, rng)

    return fn


def value_and_grad_acc(loss_fn: LossFn, n_micro: int):
    """jax.value_and_grad(has_aux=True) with microbatch gradient accumulation.

    With n_micro > 1 the batch's leading dim is split into n_micro chunks
    scanned sequentially; activations live one chunk at a time (the standard
    pod-scale activation-memory lever). aux is reduced to its scalar metrics
    (mean over chunks) — methods needing full aux tensors (MESA) keep
    n_micro == 1.

    Bucket-resident params work transparently: the loss is view-wrapped, and
    the accumulation arithmetic (`tree_zeros_like`, leafwise adds/casts) maps
    over the buffers themselves.
    """
    loss_fn = view_loss(loss_fn)
    if n_micro <= 1:
        return jax.value_and_grad(loss_fn, has_aux=True)

    def fn(params, batch, rng):
        def chunked(x):
            b = x.shape[0]
            assert b % n_micro == 0, (b, n_micro)
            return x.reshape(n_micro, b // n_micro, *x.shape[1:])

        chunks = jax.tree.map(chunked, batch)

        def body(carry, chunk):
            loss_sum, grad_sum = carry
            (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, chunk, rng)
            scal = {k: v for k, v in aux.items()
                    if isinstance(v, jax.Array) and v.ndim == 0}
            grad_sum = jax.tree.map(
                lambda a, gi: a + gi.astype(jnp.float32), grad_sum, g)
            return (loss_sum + l, grad_sum), scal

        init = (jnp.float32(0.0), trees.tree_zeros_like(params, jnp.float32))
        (loss_sum, grad_sum), auxs = jax.lax.scan(body, init, chunks)
        grads = jax.tree.map(lambda g, p: (g / n_micro).astype(p.dtype),
                             grad_sum, params)
        aux = jax.tree.map(lambda v: jnp.mean(v, axis=0), auxs)
        return (loss_sum / n_micro, aux), grads

    return fn
