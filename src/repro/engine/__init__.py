"""repro.engine — one execution API over every training schedule.

Executor matrix:

    FusedExecutor   Form A  one SPMD program; mesh/sharding/jit/donation
    HeteroExecutor  Form B  two lanes (slow ascent thread + fast descent),
                            staleness ledger, system-aware calibration
    RemoteExecutor  Form B  same two lanes, but the ascent lane lives in
                            another process/host behind repro.service
                            (TCP/Unix sockets; loopback mode for one host)
    ElasticExecutor wrapper preemption-surviving mesh resizes around any of
                            the above (shrink onto survivors / grow with
                            capacity, driven by runtime.chaos MeshEvents)
    GuardedExecutor wrapper numerics guard around any of the above (outermost):
                            in-step skip, rho de-escalation ladder, PoisonBatch
                            rollback (runtime.guard; --guard in the launcher)

All satisfy the `StepExecutor` protocol and the `ENGINE_METRIC_KEYS`
contract; `Engine.fit` drives any of them with the same callbacks.
"""
from repro.engine.api import (  # noqa: F401
    ENGINE_METRIC_KEYS,
    ENGINE_OPTIONAL_METRIC_KEYS,
    FitReport,
    StepExecutor,
    ensure_metric_contract,
)
from repro.engine.callbacks import (  # noqa: F401
    Callback,
    CheckpointCallback,
    EvalCallback,
    LoggingCallback,
    StalenessTelemetry,
    ThroughputMeter,
)
from repro.engine.elastic import ElasticExecutor  # noqa: F401
from repro.engine.engine import Engine  # noqa: F401
from repro.engine.fused import FusedExecutor  # noqa: F401
from repro.engine.hetero import HeteroExecutor  # noqa: F401
from repro.engine.remote import RemoteExecutor  # noqa: F401
from repro.runtime.guard import GuardConfig, GuardedExecutor  # noqa: F401
