"""AsyncSAM — the paper's contribution (Algorithm 1), in two executable forms.

Form A ("fused", pod-scale default): because tau=1 removes the ascent->descent
dependency, one jitted SPMD step computes BOTH

    g_t = ∇L^b ( w_t + r * a_{t-1} / ||a_{t-1}|| )     (descent, perturbed)
    a_t = ∇L^{b'} ( w_t )                               (next ascent)

The two gradient computations are independent dataflow nodes, so XLA's
scheduler overlaps the small collective-free ascent compute with the descent
gradient's reduce-scatter — the TPU-native realization of "hide the
perturbation time" (DESIGN.md §2 A1). The carried state a_{t-1} is exactly the
asynchrony of paper Eq. 2 with tau=1.

Form B ("split", faithful heterogeneous executor): `ascent_fn` and
`descent_fn` are exposed separately so repro.runtime.async_executor can run
them on two different compute resources with a depth-1 queue, reproducing the
paper's MPI two-process scheme including system-aware b' calibration and
straggler fallback.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.perturb import (gradient_norm_penalty_direction,
                                perturb as _perturb, perturb_masked as _perturb_masked)
from repro.core.api import (LossFn, Method, MethodConfig, TrainState, _finish,
                            step_rng, value_and_grad_acc)
from repro.core.ascent import Compressor, CompressionState, slice_ascent_batch, split_batch
from repro.core.sam import _m
from repro.optim import GradientTransform
from repro.utils import buckets, trees

Pytree = Any


class AsyncSamState(NamedTuple):
    """Carry across steps: the (possibly compressed) ascent gradient a_{t-tau}."""
    ascent_grad: Pytree            # a_{t-1}; zeros before the first refresh
    ascent_norm: jax.Array         # ||a_{t-1}|| (fp32 scalar)
    have_ascent: jax.Array         # bool scalar: a valid gradient is held
    staleness: jax.Array           # int32: age of the held gradient (tau)
    compression: CompressionState  # error-feedback residual ((), when disabled)


def _init_state(params: Pytree, compressor: Compressor) -> AsyncSamState:
    return AsyncSamState(
        ascent_grad=trees.tree_zeros_like(params, jnp.float32),
        ascent_norm=jnp.zeros((), jnp.float32),
        have_ascent=jnp.zeros((), jnp.bool_),
        staleness=jnp.zeros((), jnp.int32),
        compression=compressor.init(params),
    )


def make_async_sam(cfg: MethodConfig) -> Method:
    compressor = Compressor(kind=cfg.compressor, topk_fraction=cfg.topk_fraction)

    def init(params, rng):
        return _init_state(params, compressor)

    def make_step(loss_fn: LossFn, optimizer: GradientTransform):
        vg = value_and_grad_acc(loss_fn, cfg.n_microbatches)

        def step(state: TrainState, batch):
            batch, ascent_batch = split_batch(batch)
            if ascent_batch is None:
                ascent_batch = slice_ascent_batch(batch, cfg.ascent_fraction)
            ms: AsyncSamState = state.method_state
            rng = step_rng(state)
            rng_d, rng_a = jax.random.split(rng)

            # --- perturb with the STALE gradient a_{t-1} (Algorithm 1, line 5).
            # At t=0 no ascent gradient exists: rho_eff=0 degrades to SGD
            # (Algorithm 1, line 8) without a traced branch.
            with jax.named_scope("perturb"):
                rho_eff = jnp.where(ms.have_ascent, cfg.rho, 0.0)
                w_hat = _perturb(state.params, ms.ascent_grad, rho_eff,
                                 grad_norm=ms.ascent_norm,
                                 fused=cfg.fused_update)

            # --- descent gradient at the perturbed point (line 6).
            with jax.named_scope("descent"):
                (loss, aux), grads = vg(w_hat, batch, rng_d)

            # --- NEXT ascent gradient at the *unperturbed* current params
            # (line 3; independent of the descent computation above).
            # ascent_interval > 1 (beyond-paper "AsyncSAM-k") refreshes only
            # every k-th step: average extra compute drops to f/k while tau
            # grows to at most k — EXPERIMENTS §Perf validates the accuracy.
            # the b' value-and-grad and the ascent-state refresh
            with jax.named_scope("ascent"):
                if cfg.ascent_interval <= 1:
                    (loss_asc, _), a_new = vg(state.params, ascent_batch,
                                              rng_a)
                    staleness = jnp.ones((), jnp.int32)
                    reused = jnp.zeros((), jnp.float32)
                else:
                    def fresh(_):
                        (la, _), a = vg(state.params, ascent_batch, rng_a)
                        return (trees.tree_cast(a, jnp.float32), la,
                                jnp.int32(1))

                    def reuse(_):
                        # ascent_loss is a NaN SENTINEL here (no ascent
                        # pass ran, there is no loss to report); the explicit
                        # ascent_reused flag below is what disambiguates it
                        # from a genuine NaN
                        return (ms.ascent_grad, jnp.float32(jnp.nan),
                                ms.staleness + 1)

                    refresh = (state.step % cfg.ascent_interval) == 0
                    a_new, loss_asc, staleness = jax.lax.cond(
                        refresh, fresh, reuse, None)
                    reused = (~refresh).astype(jnp.float32)

                # --- ascent-state refresh. On the fused path the cosine
                # metric and the carried norm come from ONE pass over (a_t,
                # a_{t-1}) (kernels.fused_dot_norms) instead of three per-leaf
                # reductions; lossless only, since compression changes the
                # stored gradient. With bucket-resident state both operands
                # already ARE buffers (a_new differentiated through the params
                # view, ascent_grad carried resident), so the refresh is
                # buffer -> buffer.
                resident = buckets.is_bucketed(state.params)
                if ((resident
                     or buckets.fused_path_enabled(cfg.fused_update))
                        and cfg.compressor == "none"):
                    a32 = trees.tree_cast(a_new, jnp.float32)
                    layout = (state.params.layout if resident
                              else buckets.bucket_layout(a32))
                    dot, sq_new, sq_old = buckets.bucketed_dot_norms(
                        a32, ms.ascent_grad, layout=layout)
                    cos = dot / (jnp.sqrt(sq_new) * jnp.sqrt(sq_old)
                                 + 1e-12)
                    comp_state = ms.compression
                    new_ms = AsyncSamState(
                        ascent_grad=a32,
                        ascent_norm=jnp.sqrt(sq_new),
                        have_ascent=jnp.ones((), jnp.bool_),
                        staleness=staleness,
                        compression=comp_state,
                    )
                else:
                    cos = trees.tree_cosine_similarity(a_new, ms.ascent_grad)
                    a_lossy, comp_state = compressor.compress(
                        a_new, ms.compression)
                    new_ms = AsyncSamState(
                        ascent_grad=trees.tree_cast(a_lossy, jnp.float32),
                        ascent_norm=trees.global_norm(a_lossy),
                        have_ascent=jnp.ones((), jnp.bool_),
                        staleness=staleness,
                        compression=comp_state,
                    )
                if cfg.guard_update:
                    # keep a non-finite ascent refresh out of the CARRIED
                    # state: a NaN a_t held across steps poisons every later
                    # perturbation (0 * NaN is still NaN), so the refresh is
                    # guarded by its own finiteness, independent of the
                    # descent verdict in _finish
                    ok_a = jnp.isfinite(new_ms.ascent_norm)
                    new_ms = jax.tree.map(
                        lambda n, o: jnp.where(ok_a, n, o), new_ms, ms)
            metrics = {"loss": loss, "ascent_loss": loss_asc,
                       "ascent_norm": new_ms.ascent_norm,
                       "ascent_cosine": cos,
                       "ascent_reused": reused,
                       "perturbed": ms.have_ascent.astype(jnp.float32),
                       **_m(aux)}
            # _finish runs the optimizer tail under its own "update" scope
            return _finish(state, optimizer, grads, new_ms, metrics,
                           guard=cfg.guard_update)

        return step

    return Method("async_sam", init, make_step)


# ---------------------------------------------------------------------------
# Split-phase API (Form B) — used by the heterogeneous async executor.
# ---------------------------------------------------------------------------

def make_ascent_fn(loss_fn: LossFn) -> Callable:
    """Jittable ascent phase: params, batch, rng -> (grad fp32, norm, loss).

    Runs on the *slow* resource (paper: CPU). Collective-free. Params arrive
    pytree-shaped (the lane hand-off / wire contract; the executor converts a
    bucket-resident snapshot at the edge).
    """
    def ascent(params, batch, rng):
        with jax.named_scope("ascent"):
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch, rng)
            g = trees.tree_cast(g, jnp.float32)
            return g, trees.global_norm(g), loss

    return ascent


def make_descent_fn(cfg: MethodConfig, loss_fn: LossFn,
                    optimizer: GradientTransform) -> Callable:
    """Jittable descent phase: one model update given a held ascent gradient.

    (state, batch, a, a_norm, have_a) -> (state, metrics). `have_a=False`
    (straggler fallback past max staleness) degrades the step to plain SGD.
    With bucket-resident state, `a` still arrives pytree-shaped from the lane
    (the cross-resource hand-off); perturb gathers it once against the
    resident layout and everything downstream stays buffer -> buffer.
    """
    vg = value_and_grad_acc(loss_fn, 1)

    def descent(state: TrainState, batch, a: Pytree, a_norm: jax.Array,
                have_a: jax.Array):
        batch, _ = split_batch(batch)
        with jax.named_scope("perturb"):
            rho_eff = jnp.where(have_a, cfg.rho, 0.0)
            w_hat = _perturb(state.params, a, rho_eff, grad_norm=a_norm,
                             fused=cfg.fused_update)
        with jax.named_scope("descent"):
            (loss, aux), grads = vg(w_hat, batch, step_rng(state))
        return _finish(state, optimizer, grads, state.method_state,
                       {"loss": loss, **_m(aux)}, guard=cfg.guard_update)

    return descent
