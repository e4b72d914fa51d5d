"""Engine — the single fit loop every entrypoint drives.

    executor = FusedExecutor(loss_fn, mcfg, opt)            # or HeteroExecutor
    state = executor.init_state(params, rng)
    with Engine(executor, pipeline, callbacks=[LoggingCallback()]) as eng:
        report = eng.fit(state, steps=1000)

The Engine owns iteration, timing, callback dispatch, and the optional
pre-fit hook (hetero calibration); a `CheckpointCallback` routes the loop
through `runtime.run_resilient` so checkpoint-restart fault tolerance is the
same code path with or without the Engine. `data` is any iterable of batches;
the resilient path additionally needs the pipeline `state()/restore()`
protocol (see repro.data.pipeline).
"""
from __future__ import annotations

import time
from typing import Any, Iterable, Optional, Sequence

import jax

from repro.core import TrainState
from repro.engine.api import FitReport, StepExecutor
from repro.engine.callbacks import Callback, CheckpointCallback
from repro.obs import Tracker, current_tracker, scalar_metrics, use_tracker
from repro.runtime import run_resilient

Pytree = Any


class Engine:
    def __init__(self, executor: StepExecutor, data: Iterable[dict],
                 callbacks: Sequence[Callback] = ()):
        self.executor = executor
        self.data = data
        self.callbacks = list(callbacks)
        self.pre_fit_report: Optional[dict] = None

    # --- plumbing -------------------------------------------------------------
    def _probe_batch(self) -> dict:
        """A batch for calibration probes, without advancing the cursor when
        the pipeline supports peek() (lists/tuples are naturally re-iterable;
        a bare generator loses the probe batch — give it peek() if that
        matters for restart determinism)."""
        peek = getattr(self.data, "peek", None)
        if peek is not None:
            return peek()
        it = iter(self.data)
        try:
            return next(it)
        finally:
            if hasattr(it, "close"):
                it.close()

    def _wrapped_step(self):
        def step(state: TrainState, batch: dict):
            trk = current_tracker()
            t0 = time.perf_counter()
            at = _read_step(trk, state)
            with trk.span("train_step", lane="descent", step=at):
                state, metrics = self.executor.step(state, batch)
            dt = time.perf_counter() - t0
            trk.log({**_read_metrics(trk, metrics), "step_time_s": dt},
                    step=_read_step(trk, state))
            with trk.span("callbacks", lane="descent"):
                for cb in self.callbacks:
                    cb.on_step(self, state, metrics, dt)
            return state, metrics

        return step

    # --- the loop -------------------------------------------------------------
    def fit(self, state: TrainState, steps: int, *, warmup: int = 0,
            failure_injector=None, events=None,
            tracker: Optional[Tracker] = None) -> FitReport:
        """Train until `state.step == steps`; returns a FitReport.

        warmup: steps executed before the clock starts and before
        `on_fit_start` fires (benchmarks exclude compile time this way).

        events: a MeshEvent source (`runtime.chaos.ChaosSchedule` or a
        production capacity watcher). With an `ElasticExecutor` it is
        attached to the executor, which drains it before each step (graceful
        resizes in-band; crash events through the restore path — those need
        a `CheckpointCallback`). With any other executor a *callable* source
        degrades to the failure-injector surface: its crash events raise,
        its resizes are skipped — the generalization of `failure_injector`.

        tracker: a `repro.obs.Tracker`; installed as the process-global
        current tracker for the duration of the fit, so executor internals
        (ascent lanes, pool workers, elastic resizes) report spans to it
        from their own threads. Without one, whatever tracker is already
        current (by default the no-op null tracker) stays in effect.
        """
        if tracker is not None:
            with use_tracker(tracker):
                return self._fit(state, steps, warmup=warmup,
                                 failure_injector=failure_injector,
                                 events=events)
        return self._fit(state, steps, warmup=warmup,
                         failure_injector=failure_injector, events=events)

    def _fit(self, state: TrainState, steps: int, *, warmup: int,
             failure_injector, events) -> FitReport:
        if events is not None:
            attach = getattr(self.executor, "attach_events", None)
            if attach is not None:
                attach(events)
            elif callable(events):
                if failure_injector is not None:
                    raise ValueError("pass either events or failure_injector "
                                     "to a non-elastic executor, not both")
                failure_injector = events
            else:
                raise ValueError(
                    f"{type(self.executor).__name__} cannot consume a "
                    "MeshEvent source; wrap it in ElasticExecutor or pass a "
                    "callable failure injector")
        hook = getattr(self.executor, "pre_fit", None)
        if hook is not None and getattr(self.executor, "wants_pre_fit", True):
            self.pre_fit_report = hook(state, self._probe_batch())

        ckpt = next((c for c in self.callbacks
                     if isinstance(c, CheckpointCallback)), None)
        if warmup and ckpt is not None:
            # run_resilient re-iterates the pipeline from its cursor; a
            # separate warmup iterator would replay (list data) or orphan a
            # prefetch worker (pipeline data)
            raise ValueError("warmup is not supported with CheckpointCallback")

        it = None
        if warmup:
            it = iter(self.data)
            try:
                for _ in range(warmup):
                    state, _ = self.executor.step(state, next(it))
            except BaseException:
                if hasattr(it, "close"):
                    it.close()   # don't leak the prefetch worker on a
                raise            # failing warmup step

        try:
            for cb in self.callbacks:
                cb.on_fit_start(self, state)
        except BaseException:
            if it is not None and hasattr(it, "close"):
                it.close()   # a raising callback must not orphan the
            raise            # warmup iterator's prefetch worker
        wrapped = self._wrapped_step()
        if ckpt is not None:
            rep = run_resilient(wrapped, state, self.data, ckpt.manager, steps,
                                ckpt.resilience, failure_injector,
                                shardings=ckpt.shardings,
                                on_restore=getattr(self.executor,
                                                   "on_restore", None))
            report = FitReport(final_state=rep.final_state,
                               steps_done=rep.steps_done,
                               restarts=rep.restarts,
                               metrics_history=rep.metrics_history,
                               wall_time_s=rep.wall_time_s,
                               pre_fit=self.pre_fit_report,
                               poison_rollbacks=rep.poison_rollbacks)
        else:
            t0 = time.time()
            history: list = []
            it = it if it is not None else iter(self.data)
            trk = current_tracker()
            try:
                # one iteration per "step" span: draw, step, history, and
                # the read of the step counter that the next test compares
                at = _read_step(trk, state)
                while at < steps:
                    with trk.span("step", lane="descent", step_num=at):
                        try:
                            batch = next(it)
                        except StopIteration:
                            break
                        state, metrics = wrapped(state, batch)
                        history.append(_read_metrics(trk, metrics))
                        at = _read_step(trk, state)
            finally:
                if hasattr(it, "close"):
                    it.close()   # stop a prefetching pipeline's worker now
            report = FitReport(final_state=state, steps_done=int(state.step),
                               restarts=0, metrics_history=history,
                               wall_time_s=time.time() - t0,
                               pre_fit=self.pre_fit_report)

        for cb in self.callbacks:
            cb.on_fit_end(self, report)
        return report

    # --- lifecycle ------------------------------------------------------------
    def close(self) -> None:
        self.executor.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _read_step(trk: Tracker, state: TrainState) -> int:
    """`state.step` on the host: one device-to-host read, in its span."""
    with trk.span("readback", lane="descent", of="step", n=1):
        return int(state.step)


def _read_metrics(trk: Tracker, metrics: dict) -> dict:
    """`scalar_metrics(metrics)` in a span counting the device scalars it
    reads back."""
    n = sum(isinstance(v, jax.Array) and v.ndim == 0
            for v in metrics.values())
    with trk.span("readback", lane="descent", of="metrics", n=n):
        return scalar_metrics(metrics)
