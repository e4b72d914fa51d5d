"""FusedExecutor — Form A: one jitted SPMD step per training iteration.

Wraps `Method.make_step` together with the mesh/sharding/jit/donation plumbing
that used to be inlined in `launch/train.py`: with a mesh it enters the
ambient-mesh + activation-sharding contexts, shards the TrainState by
`launch.sharding.state_spec_tree`, and jits with donated input state and
explicit out_shardings; without a mesh it is a plain single-device jit, which
is what the CPU benchmarks and unit tests use. Either way the caller sees only
the `StepExecutor` surface.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import Method, MethodConfig, TrainState, init_train_state, make_method
from repro.core.api import LossFn
from repro.core.async_sam import AsyncSamState
from repro.engine.api import ensure_metric_contract
from repro.obs import current_tracker
from repro.optim import GradientTransform, configure_fused
from repro.utils import buckets

Pytree = Any

# Methods whose steps are pure weight-space + value_and_grad compositions —
# safe to run on bucket-resident state. The others (esam's per-leaf masks via
# tree_paths, mesa's EMA distill, ...) keep the pytree representation.
RESIDENT_METHODS = ("sgd", "sam", "gsam", "async_sam")


class FusedExecutor:
    """Single-resource executor: the whole step is one XLA program.

    Args:
      loss_fn: framework loss callback `(params, batch, rng) -> (loss, aux)`.
      method: a `MethodConfig` (name-dispatched) or an already-built `Method`.
      optimizer: inner gradient transform.
      mesh: when given, run under this mesh with sharded state + donation
        (the pod/production path); when None, plain jit (CPU smoke path).
      model_cfg: ModelConfig used by the sharding rules; required with `mesh`.
      donate: donate the input TrainState buffers to the step (in-place
        update at scale; safe because callers rebind `state` every step).
      block: block on the updated params each step so host-side timing and
        callbacks see real step latency (all previous loops did this).
      fused_update: flat-buffer fused weight-space path (perturb + optimizer
        epilogue on dtype-bucketed buffers via single-pass kernels). None
        resolves to the platform default — on for TPU when the step runs
        unsharded (mesh None or 1 device; flattening a model-sharded leaf
        would force an all-gather under pjit), off elsewhere. The resolved
        flag is pinned into both the MethodConfig and the optimizer's
        FusedSpec before the step is built, so it is trace-time static.
      resident: bucket-RESIDENT training state — params / optimizer moments /
        ascent state live as persistent dtype buckets (buckets.BucketedState)
        and the step is buffer -> buffer, with donate=True aliasing input
        buffers to output buffers so no per-step gather/scatter copies
        remain (the realized counterpart of the fused path's modeled HBM
        win). None follows the resolved fused_update whenever the whole
        chain qualifies: meshless (or 1-device-mesh) step, a
        RESIDENT_METHODS method with an uncompressed ascent exchange, and a
        FusedSpec-recognized optimizer.
        Checkpoints stay pytree-shaped at the boundary (run_resilient
        converts at the edge), so resident and per-leaf runs interoperate.
    """

    name = "fused"

    def __init__(self, loss_fn: LossFn,
                 method: Union[Method, MethodConfig, None] = None,
                 optimizer: Optional[GradientTransform] = None, *,
                 mesh=None, model_cfg=None, donate: bool = True,
                 block: bool = True, fused_update: Optional[bool] = None,
                 resident: Optional[bool] = None):
        assert optimizer is not None, "FusedExecutor needs an optimizer"
        if fused_update is None:
            fused_update = (jax.default_backend() == "tpu"
                            and (mesh is None or mesh.size == 1))
        self.fused_update = fused_update
        optimizer = configure_fused(optimizer, fused_update)
        if isinstance(method, Method):
            # pre-built Method: rebuild from its attached config so the step's
            # perturb/refresh call sites see the RESOLVED flag (a None in the
            # closure would re-resolve to the bare platform default — fusing
            # sharded-mesh perturbs on TPU that this executor just declined).
            # A hand-constructed Method without cfg is taken as-is.
            if (method.cfg is not None
                    and method.cfg.fused_update != fused_update):
                self.method = make_method(dataclasses.replace(
                    method.cfg, fused_update=fused_update))
            else:
                self.method = method
        else:
            mcfg = dataclasses.replace(method or MethodConfig(),
                                       fused_update=fused_update)
            self.method = make_method(mcfg)
        if resident is None:
            mcfg = self.method.cfg
            # mesh.size == 1 qualifies like fused_update's own auto rule does
            # (the launcher always passes a host mesh, 1-device on one chip)
            resident = (fused_update and (mesh is None or mesh.size == 1)
                        and self.method.name in RESIDENT_METHODS
                        and getattr(optimizer, "fused_spec", None) is not None
                        and (mcfg is None or mcfg.compressor == "none"))
        if resident and mesh is not None and mesh.size > 1:
            # flattening a model-sharded leaf into a global bucket would force
            # an all-gather under pjit; per-shard bucketing is the ROADMAP
            # follow-on, so a sharded mesh keeps the pytree representation
            raise ValueError("bucket-resident state needs an unsharded step "
                             f"(mesh size {mesh.size}); use resident=False or "
                             "drop the mesh")
        self.resident = bool(resident)
        self.optimizer = optimizer
        self.mesh = mesh
        self.model_cfg = model_cfg
        self.donate = donate
        self.block = block
        self._step_raw = self.method.make_step(loss_fn, optimizer)
        self._jitted = None
        self._closed = False
        if mesh is not None:
            assert model_cfg is not None, "mesh sharding needs the ModelConfig"

    def _scope(self) -> contextlib.AbstractContextManager:
        """Ambient mesh + activation-sharding rules, entered per call.

        Scoping each init/step call (instead of holding the process-global
        contexts from __init__ to close) means an error before the Engine
        takes ownership can never leak a stale mesh into later jax work, and
        two live executors never interleave their context frames.
        """
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.models.partitioning import activation_sharding
        stack = contextlib.ExitStack()
        stack.enter_context(jax.set_mesh(self.mesh))
        stack.enter_context(activation_sharding(self.mesh))
        return stack

    def _residentize_params(self, params: Pytree) -> Pytree:
        """Gather params into persistent buckets (once, at state birth);
        optimizer.init / method.init then produce congruent resident moments
        and ascent state by mapping over the buffers."""
        if self.resident and not buckets.is_bucketed(params):
            return buckets.BucketedState.from_tree(params)
        return params

    # --- StepExecutor ---------------------------------------------------------
    def init_state(self, params: Pytree, rng: jax.Array) -> TrainState:
        donate = (0,) if self.donate else ()
        with self._scope():
            params = self._residentize_params(params)
            state = init_train_state(params, self.optimizer, self.method, rng)
            if self.mesh is None:
                self._jitted = jax.jit(self._step_raw, donate_argnums=donate)
                return state
            from repro.launch.sharding import state_spec_tree, to_named
            state_sh = to_named(state_spec_tree(jax.eval_shape(lambda: state),
                                                self.model_cfg, self.mesh),
                                self.mesh)
            state = jax.device_put(state, state_sh)
            self._jitted = jax.jit(self._step_raw, donate_argnums=donate,
                                   out_shardings=(state_sh, None))
            return state

    def abstract_state(self, params_fn, rng: jax.Array) -> TrainState:
        """ShapeDtypeStruct TrainState — no device allocation (dry-run entry).

        `params_fn` builds the parameter pytree; it only ever runs under
        `jax.eval_shape`, so a full-size production config costs nothing.
        With `resident`, the abstract state carries BucketedState nodes, so
        `lower` pins the same buffer-shaped signature (and donation aliasing)
        the live step runs with.
        """
        with self._scope():
            return jax.eval_shape(lambda: init_train_state(
                self._residentize_params(params_fn()), self.optimizer,
                self.method, rng))

    def lower(self, state_sds, batch_sds):
        """Jit-lower the step with explicit in/out shardings (compile
        analysis / multi-pod dry-run — the same plumbing init_state uses,
        but against abstract operands and with pinned input shardings)."""
        donate = (0,) if self.donate else ()
        with self._scope():
            if self.mesh is None:
                return jax.jit(self._step_raw, donate_argnums=donate
                               ).lower(state_sds, batch_sds)
            from repro.launch.sharding import (batch_spec_tree,
                                               state_spec_tree, to_named)
            state_sh = to_named(state_spec_tree(state_sds, self.model_cfg,
                                                self.mesh), self.mesh)
            batch_sh = to_named(batch_spec_tree(batch_sds, self.mesh),
                                self.mesh)
            return jax.jit(self._step_raw, in_shardings=(state_sh, batch_sh),
                           out_shardings=(state_sh, None),
                           donate_argnums=donate).lower(state_sds, batch_sds)

    def compile_step(self, state: TrainState, batch: dict):
        """Compile the step ahead of time exactly as `step` will run it (the
        same jit, the same arguments) and return the compiled program, for
        set-up timing and inspection; the first `step` reuses this compile."""
        assert self._jitted is not None, "call init_state before compile_step"
        with self._scope():
            return self._jitted.lower(state, batch).compile()

    def resize(self, state: TrainState, new_mesh) -> TrainState:
        """Elastic re-entry: re-place the live `state` onto `new_mesh` and
        re-lower the jitted step against it.

        Donation aliasing survives the resize: the fresh jit keeps the same
        `donate_argnums`, and its out_shardings are recomputed for the new
        mesh, so the first post-resize step already aliases input buffers to
        output buffers. Bucket-resident state stays resident — the bucket
        layout is mesh-independent (`buckets.rebucket` is an identity
        re-group here) and the target must be unsharded, same constraint as
        construction (per-shard bucketing is the ROADMAP follow-on); the
        placement of the whole buffers is a single replicated device_put.
        Non-resident state re-places leaf-by-sharding-rule exactly like
        `init_state`, device-to-device (the survivors already hold their
        shards — no host round-trip).
        """
        assert not self._closed, "executor is closed"
        donate = (0,) if self.donate else ()
        if self.resident:
            if new_mesh is not None and new_mesh.size > 1:
                raise ValueError(
                    "bucket-resident step cannot resize onto a sharded mesh "
                    f"(size {new_mesh.size}); per-shard bucketing is the "
                    "ROADMAP follow-on — rebuild with resident=False to "
                    "resize across sharded meshes")
            # layout is mesh-independent: rebucket is the identity re-group,
            # re-asserted here so a layout-changing source (per-shard
            # buckets, someday) flows through the same edge
            state = jax.tree.map(
                lambda n: (buckets.rebucket(n, n.layout)
                           if buckets.is_bucketed(n) else n),
                state, is_leaf=buckets.is_bucketed)
            self.mesh = None   # a 1-device mesh adds nothing over meshless
            self._jitted = jax.jit(self._step_raw, donate_argnums=donate)
            return state
        if new_mesh is not None and self.model_cfg is None:
            raise ValueError("resize onto a mesh needs the ModelConfig "
                             "(construct the executor with model_cfg=...)")
        self.mesh = new_mesh
        with self._scope():
            if new_mesh is None:
                state = jax.device_put(state)
                self._jitted = jax.jit(self._step_raw, donate_argnums=donate)
                return state
            from repro.launch.sharding import state_spec_tree, to_named
            state_sh = to_named(state_spec_tree(jax.eval_shape(lambda: state),
                                                self.model_cfg, new_mesh),
                                new_mesh)
            state = jax.device_put(state, state_sh)
            self._jitted = jax.jit(self._step_raw, donate_argnums=donate,
                                   out_shardings=(state_sh, None))
            return state

    def step(self, state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        assert self._jitted is not None, "call init_state before step"
        assert not self._closed, "executor is closed"
        trk = current_tracker()
        with trk.span("dispatch", lane="descent"), self._scope():
            state, metrics = self._jitted(state, batch)
        if self.block:
            with trk.span("device_wait", lane="descent"):
                jax.block_until_ready(state.params)
        ms = state.method_state
        tau = (ms.staleness if isinstance(ms, AsyncSamState)
               else jnp.zeros((), jnp.int32))
        return state, ensure_metric_contract(
            metrics, tau=tau,
            perturbed=0.0 if self.method.name == "sgd" else 1.0)

    def close(self) -> None:
        # nothing held between calls (scopes are per-call); closing only
        # fences off further step() calls
        self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
