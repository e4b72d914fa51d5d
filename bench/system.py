"""The system under test, driven the way a training job drives it.

`ProgramSystem` builds what `repro.launch.train` builds: `TokenPipeline`
(fed by this benchmark's `TokenSource`) -> `FusedExecutor` on the cell's
("data", "model") mesh -> `Engine.fit`. The weights are the configuration's
architecture's (`bench/archs/<arch>.py`, by its "arch"), made from the seed
and placed by the program's own sharding rules (`launch.sharding`), as a
training job places them. Set-up drives that one engine through
its first three steps (compiling on the first) and records, for the
correctness check, each step's loss, each leaf's first gradient as AdamW
received it (its first moment after one step over 1 - b1) and the parameters
after step 3. The window then drives the same engine, on the same state and
feed, until its time is up. Timing wrappers around the feed and the executor
record what the per-layer metrics read; nothing in the program is changed.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import generator, load, weights

CHECK_STEPS = 3


def ascent_rows(cell: dict) -> int:
    """b': the pipeline's own rounding of batch x ascent_fraction."""
    if cell["method"] != "async_sam":
        return 0
    return max(1, round(cell["batch"] * cell["ascent_fraction"]))


def model_dims(config: dict) -> dict:
    """The widths the reference needs, from a configuration file."""
    return {**config["model"], "norm_eps": config["norm_eps"]}


class Feed:
    """The pipeline as `Engine` iterates it, timed; with `seconds` set, an
    iteration stops drawing batches that long after it began.

    Applies `alter` (a fault planted where the batch is produced, by a test)
    and then records the first `record` batches (host copies): the
    reference follows the program over exactly these.
    """

    def __init__(self, pipeline, record: int = 0,
                 alter: Optional[Callable[[dict], dict]] = None):
        self.pipeline = pipeline
        self.record = record
        self.alter = alter
        self.recorded: list[dict] = []
        self.waits: list[float] = []
        self.seconds: Optional[float] = None
        self.t0 = 0.0

    def __iter__(self):
        it = iter(self.pipeline)
        self.t0 = time.perf_counter()
        deadline = None if self.seconds is None else self.t0 + self.seconds
        try:
            while deadline is None or time.perf_counter() < deadline:
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.data_wait"):
                    batch = next(it)
                self.waits.append(time.perf_counter() - t0)
                if self.alter is not None:
                    batch = self.alter(batch)
                if len(self.recorded) < self.record:
                    self.recorded.append(jax.device_get(batch))
                yield batch
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()


class TimedExecutor:
    """Times each `executor.step` call; everything else passes through.

    `alter` plants a fault inside the step, after the feed recorded the
    batch (tests and calibration only).
    """

    def __init__(self, inner, alter: Optional[Callable[[dict], dict]] = None):
        self.inner = inner
        self.alter = alter
        self.step_s: list[float] = []

    def step(self, state, batch):
        if self.alter is not None:
            batch = self.alter(batch)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            out = self.inner.step(state, batch)
        self.step_s.append(time.perf_counter() - t0)
        return out

    def __getattr__(self, name):
        return getattr(self.inner, name)


class Recorder:
    """Engine callback: when each step returned, and the check's readings."""

    def __init__(self, b1: float):
        self.b1 = b1
        self.checking = True
        self.times: list[float] = []
        self.losses: list[float] = []
        self.g1: list[float] = []
        self.first_step = 0.0
        self.params3 = None

    def on_fit_start(self, engine, state):
        pass

    def on_step(self, engine, state, metrics, step_time_s):
        with jax.profiler.TraceAnnotation("bench.callback"):
            jax.block_until_ready(state.params)
            self.times.append(time.perf_counter())
            if not self.checking:
                return
            self.losses.append(float(metrics["loss"]))
            if len(self.losses) == 1:
                self.first_step = time.perf_counter()
                self.g1 = [n / (1.0 - self.b1) for n in _leaf_norms(
                    _adam_mu(state.opt_state))]
            if len(self.losses) == CHECK_STEPS:
                from repro.utils import buckets
                self.params3 = jax.tree.leaves(
                    buckets.host_portable(state.params))

    def on_fit_end(self, engine, report):
        pass


def _adam_mu(opt_state):
    from repro.optim.base import AdamState
    adam, = (s for s in opt_state if isinstance(s, AdamState))
    return adam.mu


@jax.jit
def _norms(tree):
    from repro.utils import buckets
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(buckets.to_portable(tree))]


def _leaf_norms(tree) -> list[float]:
    return [float(x) for x in _norms(tree)]


def make_mesh(shape: list):
    """The cell's ("data", "model") mesh over the first chips."""
    from jax.sharding import AxisType, Mesh
    devices = np.array(jax.devices()[:math.prod(shape)]).reshape(shape)
    return Mesh(devices, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


class ProgramSystem:
    """One engine over one state and one feed, from set-up to the window."""

    def __init__(self, cell: dict, config: dict, seed: int, *,
                 alter_feed: Optional[Callable[[dict], dict]] = None,
                 alter_step: Optional[Callable[[dict], dict]] = None):
        from repro.core import MethodConfig
        from repro.data import PipelineConfig, TokenPipeline
        from repro.engine import Engine, FusedExecutor
        from repro.launch.sharding import state_spec_tree, to_named
        from repro.models import build_model
        from repro.models.config import ModelConfig
        from repro.optim import make_optimizer

        train = cell["train"]
        self.cell, self.config, self.seed = cell, config, seed
        self.marks: list[tuple[str, float]] = []
        mc = ModelConfig(**config["model"])
        self.mesh = make_mesh(cell["mesh"])
        arch = load.arch(config["arch"])
        shapes = jax.eval_shape(lambda: arch.init_params(
            jax.random.PRNGKey(0), config["model"]))
        shardings = to_named(state_spec_tree(shapes, mc, self.mesh),
                             self.mesh)
        params = weights.make_params(seed, arch, config["model"], shardings)
        jax.block_until_ready(params)
        self.marks.append(("weights made", time.perf_counter()))
        method = MethodConfig(name=cell["method"], rho=train["rho"],
                              ascent_fraction=cell["ascent_fraction"])
        optimizer = make_optimizer(
            "adamw", train["lr"], b1=train["b1"], b2=train["b2"],
            eps=train["eps"], weight_decay=train["weight_decay"],
            clip_norm=train["clip_norm"])
        executor = FusedExecutor(build_model(mc).loss_fn, method, optimizer,
                                 mesh=self.mesh, model_cfg=mc)
        self.executor = TimedExecutor(executor, alter_step)
        pipeline = TokenPipeline(mc, PipelineConfig(
            global_batch=cell["batch"], seq_len=cell["seq"], seed=seed,
            ascent_fraction=(cell["ascent_fraction"]
                             if cell["method"] == "async_sam" else 0.0)),
            source=generator.TokenSource(seed, mc.vocab_size))
        self.feed = Feed(pipeline, record=CHECK_STEPS, alter=alter_feed)
        self.recorder = Recorder(train["b1"])
        self.engine = Engine(self.executor, self.feed, [self.recorder])
        self.state = executor.init_state(
            params, jax.random.fold_in(weights.seed_key(seed), 1))
        del params
        jax.block_until_ready(self.state)
        self.marks.append(("state built", time.perf_counter()))

    def set_up(self) -> dict:
        """The first steps, through the window's own engine and feed; returns
        the program's readings for the check."""
        report = self.engine.fit(self.state, CHECK_STEPS)
        self.state = report.final_state
        self.marks.append((f"{CHECK_STEPS} steps", time.perf_counter()))
        self.recorder.checking = False
        rec = self.recorder
        self.marks.insert(-1, ("first step", rec.first_step))
        return {"loss": rec.losses, "g1": rec.g1, "params3": rec.params3,
                "batches": self.feed.recorded}

    def window(self, seconds: float) -> dict:
        """Drive the engine until `seconds` have passed; host timings."""
        rec, feed, ex = self.recorder, self.feed, self.executor
        n_times, n_waits, n_steps = len(rec.times), len(feed.waits), len(
            ex.step_s)
        feed.seconds = seconds
        with jax.profiler.TraceAnnotation("bench.window"):
            report = self.engine.fit(self.state, 2**31 - 1)
        self.state = report.final_state
        feed.seconds = None
        return {"t0": feed.t0, "times": rec.times[n_times:],
                "waits": feed.waits[n_waits:],
                "step_s": ex.step_s[n_steps:],
                "losses": [m["loss"] for m in report.metrics_history]}

    def close(self) -> None:
        self.engine.close()
        self.state = None
        gc.collect()
