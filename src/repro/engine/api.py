"""Engine API: one execution contract for every training schedule.

The paper's two realizations of AsyncSAM — the fused SPMD step (Form A,
`core/async_sam.py`) and the heterogeneous two-lane executor (Form B,
`runtime/async_executor.py`) — used to expose incompatible interfaces, so the
launcher, benchmarks, and examples each hand-rolled their own
jit/sharding/logging/checkpoint loop. This module defines the single seam they
all plug into:

    executor.init_state(params, rng)  -> TrainState       (placed + ready)
    executor.step(state, batch)       -> (state, metrics)
    executor.pre_fit(state, batch)    -> dict | None      (optional: calibration)
    executor.close()                                       (idempotent)

plus the *metric contract*: every executor's step metrics include at least
`ENGINE_METRIC_KEYS` (loss, grad_norm, tau, perturbed), so callbacks,
benchmarks, and parity tests never special-case the schedule. Future
schedules (elastic meshes, multi-host lanes, new SAM variants) are new
`StepExecutor` implementations, not new training loops.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Protocol, runtime_checkable

import jax

from repro.core import TrainState
# The metric contract tuples are derived from the typed registry in
# repro.obs.registry (one MetricKey per scalar, with description/unit/source);
# re-exported here so every historical `from repro.engine.api import
# ENGINE_METRIC_KEYS` import keeps working.
from repro.obs.registry import (ENGINE_METRIC_KEYS,  # noqa: F401
                                ENGINE_OPTIONAL_METRIC_KEYS)

Pytree = Any


@runtime_checkable
class StepExecutor(Protocol):
    """Uniform execution surface over training schedules (see module doc)."""

    name: str

    def init_state(self, params: Pytree, rng: jax.Array) -> TrainState:
        """Build the TrainState, placed/sharded for this executor."""
        ...

    def step(self, state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        """One optimizer step; metrics satisfy ENGINE_METRIC_KEYS."""
        ...

    def close(self) -> None:
        """Release resources (threads, mesh contexts). Must be idempotent."""
        ...


@dataclasses.dataclass
class FitReport:
    """What Engine.fit returns; field-compatible with runtime.RunReport."""
    final_state: TrainState
    steps_done: int
    restarts: int
    metrics_history: list
    wall_time_s: float
    pre_fit: Optional[dict] = None   # executor pre-fit telemetry (calibration)
    poison_rollbacks: int = 0        # PoisonBatch restarts (numerics guard)


def ensure_metric_contract(metrics: dict, *, tau, perturbed) -> dict:
    """Fill contract keys an executor's raw step did not already emit."""
    metrics = dict(metrics)
    metrics.setdefault("tau", tau)
    metrics.setdefault("perturbed", perturbed)
    return metrics
