"""Shard-aware training data pipeline with checkpointable state.

The pipeline yields framework batches ({"tokens","labels"[,"ascent"]...}) and
owns three production concerns:

* sharding — each data-parallel rank draws a disjoint stream (rank folded
  into the sample-stream index), so the global batch is a partition, not a
  replica; under single-controller pjit (this repo's launchers) rank=0 and
  world=1 yields the full global batch which pjit shards;
* the AsyncSAM ascent sub-batch — b' fresh samples per step (paper §3.3),
  emitted under the "ascent" key so methods never slice the descent batch;
* restartability — `state()` / `restore()` capture the step cursor, so a
  restored run continues on the exact sample stream (bitwise-identical
  batches; tested in tests/test_checkpoint.py).

Host-side double-buffering (`prefetch=2`) overlaps synthesis/disk reads with
device steps.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Callable, Iterator, Optional

import jax
import numpy as np

from repro.data.synthetic import TokenTask
from repro.models.config import ModelConfig
from repro.obs import current_tracker


@dataclasses.dataclass
class PipelineConfig:
    global_batch: int
    seq_len: int
    ascent_fraction: float = 0.0    # b'/b; 0 disables the ascent sub-batch
    seed: int = 0
    rank: int = 0                   # data-parallel rank (multi-host)
    world: int = 1
    prefetch: int = 2


class TokenPipeline:
    """Synthetic-LM pipeline (swap `source` for MmapTokenDataset in prod)."""

    def __init__(self, cfg: ModelConfig, pcfg: PipelineConfig,
                 source: Optional[object] = None):
        assert pcfg.global_batch % pcfg.world == 0
        self.cfg = cfg
        self.pcfg = pcfg
        self.source = source or TokenTask(vocab_size=cfg.vocab_size,
                                          seed=pcfg.seed)
        self._step = 0
        self._local_batch = pcfg.global_batch // pcfg.world
        b_asc = max(1, round(pcfg.global_batch * pcfg.ascent_fraction))
        self._local_ascent = max(1, b_asc // pcfg.world) if pcfg.ascent_fraction else 0

    # --- checkpointable cursor ------------------------------------------------
    def state(self) -> dict:
        # rank/world are identity, not cursor: restoring rank 0's checkpoint
        # into rank 1's pipeline would silently resume on the WRONG disjoint
        # stream shard — restore() refuses instead
        return {"step": self._step, "seed": self.pcfg.seed,
                "rank": self.pcfg.rank, "world": self.pcfg.world}

    def restore(self, state: dict) -> None:
        assert state["seed"] == self.pcfg.seed, "pipeline seed changed across restart"
        if "rank" in state:   # pre-identity-era states restore unchanged
            assert (state["rank"], state["world"]) == \
                (self.pcfg.rank, self.pcfg.world), \
                (f"pipeline identity changed across restart: checkpoint is "
                 f"rank {state['rank']}/{state['world']}, this pipeline is "
                 f"rank {self.pcfg.rank}/{self.pcfg.world}")
        self._step = int(state["step"])

    def peek(self) -> dict:
        """Synthesize the next batch WITHOUT advancing the cursor.

        Used by the Engine's pre-fit hooks (hetero calibration probes) so a
        timing probe never perturbs the restart-deterministic sample stream.
        """
        return self._make(self._step)

    # --- batch synthesis -------------------------------------------------------
    def _make(self, step: int) -> dict:
        # stream ids: (step, rank, lane) — descent lane 0, ascent lane 1
        stream = step * 2 * self.pcfg.world + 2 * self.pcfg.rank
        batch = self._one(self._local_batch, self.seq_len, stream)
        if self._local_ascent:
            batch["ascent"] = self._one(self._local_ascent, self.seq_len,
                                        stream + 1)
        return batch

    @property
    def seq_len(self) -> int:
        return self.pcfg.seq_len

    def _one(self, n: int, s: int, stream: int) -> dict:
        batch = self.source.batch(n, s, stream)
        extras = _family_extras(self.cfg, n, s, stream)
        batch.update(extras)
        return batch

    def __iter__(self) -> Iterator[dict]:
        if self.pcfg.prefetch <= 0:
            while True:
                batch = self._make(self._step)
                self._step += 1
                yield batch
        else:
            yield from self._prefetching()

    def _prefetching(self) -> Iterator[dict]:
        q: queue.Queue = queue.Queue(maxsize=self.pcfg.prefetch)
        stop = threading.Event()

        def worker(start_step: int):
            s = start_step
            while not stop.is_set():
                batch = self._make(s)        # synthesize once ...
                while not stop.is_set():
                    try:
                        q.put((s, batch), timeout=0.2)
                        s += 1
                        break                # ... retry only the hand-off
                    except queue.Full:
                        continue

        t = threading.Thread(target=worker, args=(self._step,), daemon=True)
        t.start()
        try:
            while True:
                with current_tracker().span("data_next", lane="data",
                                            ready=q.qsize()):
                    s, batch = q.get()
                self._step = s + 1
                yield batch
        finally:
            stop.set()
            # wake a blocked put(), then wait the worker out: a daemon thread
            # left inside jnp.asarray at interpreter exit aborts the process
            # (std::terminate from native thread teardown)
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=5.0)


def _family_extras(cfg: ModelConfig, n: int, s: int, stream: int) -> dict:
    """Modality-stub inputs (precomputed embeddings per the assignment)."""
    import jax.numpy as jnp

    rng = np.random.default_rng((stream, 99))
    extras = {}
    if cfg.vision is not None:
        extras["patch_embeds"] = jnp.asarray(rng.normal(size=(
            n, cfg.vision.n_image_tokens, cfg.vision.clip_dim)).astype(np.float32),
            dtype=jnp.dtype(cfg.compute_dtype))
    if cfg.family == "audio":
        from repro.models.registry import whisper_enc_len
        extras["enc_frames"] = jnp.asarray(rng.normal(size=(
            n, whisper_enc_len(cfg, s), cfg.d_model)).astype(np.float32),
            dtype=jnp.dtype(cfg.compute_dtype))
    return extras
