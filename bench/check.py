"""How `correct` is decided for a training cell.

The program's readings come from its own first three steps in set-up
(`system.ProgramSystem.set_up`); the reference (`reference.py`) follows the
same three steps from the same seed's weights on the batches the program was
fed. The numbers compared, each against its cell's limit:

- `loss_gap`: the worst step's |loss - reference| / |reference|;
- `grad_gap`: the worst leaf's gap between the norms of the first clipped
  gradient (the program's worked out from AdamW's first moment after one
  step), over the larger of that leaf's reference norm and the median
  leaf's;
- `change_gap`: the same for the norm of each leaf's change over the three
  steps, over the leaves whose reference gradient is at least a thousandth
  of the median leaf's (the others move under Adam by round-off alone);
- `feed_rows`: rows the program was fed that the generator did not make in
  the streams the first steps could draw, that it saw twice, or that are
  missing from or extra to the cell's batch (exact: limit 0).

A number that is not finite fails its limit.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import generator, reference, weights

NEGLIGIBLE_GRAD = 1e-3   # of the median leaf's first-gradient norm


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]


@jax.jit
def _change_norms(new, old):
    return [jnp.sqrt(jnp.sum(jnp.square(a - b)))
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]


_STEPS: dict = {}


def _reference_step(dims: dict, train: dict, precision: str):
    """The jitted reference step, one per (widths, training, precision) in
    a process, so that a process checking many seeds traces it once."""
    key = json.dumps([dims, train, precision], sort_keys=True)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(reference.make_step(dims, train, precision),
                              donate_argnums=0)
    return _STEPS[key]


def reference_readings(dims: dict, train: dict, seed: int, batches: list,
                       precision: str = "fp32", steps: int = 3) -> dict:
    """The reference's readings over `steps` steps from the seed's weights:
    each step's loss, each leaf's first clipped gradient norm, and each
    leaf's change norm over the steps."""
    params0 = weights.make_params(seed, dims)
    st = reference.init_state(jax.tree.map(jnp.copy, params0))
    step = _reference_step(dims, train, precision)
    losses, g1 = [], []
    for k, batch in enumerate(batches[:steps]):
        st, value, g = step(st, jax.tree.map(jnp.asarray, batch))
        losses.append(float(value))
        if k == 0:
            g1 = [float(x) for x in _leaf_norms(g)]
        del g
    change = [float(x) for x in _change_norms(st.params, params0)]
    return {"loss": losses, "g1": g1, "change": change}


def program_change(params3: list, dims: dict, seed: int) -> list[float]:
    """Each leaf's change norm from the seed's weights to the program's
    parameters after the check steps (host leaves, program leaf order)."""
    params0 = jax.tree.leaves(jax.device_get(weights.make_params(seed, dims)))
    return [float(np.linalg.norm((np.asarray(a, np.float32) - b).ravel()))
            for a, b in zip(params3, params0)]


def _worst_leaf_gap(got: list[float], want: list[float],
                    leaves: list[int]) -> float:
    floor = float(np.median([want[i] for i in leaves]))
    return max(abs(got[i] - want[i]) / max(want[i], floor) for i in leaves)


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers from the program's and the reference's readings
    (each {"loss", "g1", "change"}; `prog` may carry "feed_rows")."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                       ref["loss"]))
    every = list(range(len(ref["g1"])))
    med = float(np.median(ref["g1"]))
    moved = [i for i in every if ref["g1"][i] >= NEGLIGIBLE_GRAD * med]
    out = {"loss_gap": loss_gap,
           "grad_gap": _worst_leaf_gap(prog["g1"], ref["g1"], every),
           "change_gap": _worst_leaf_gap(prog["change"], ref["change"],
                                         moved)}
    if "feed_rows" in prog:
        out["feed_rows"] = prog["feed_rows"]
    if len(prog["loss"]) != len(ref["loss"]):
        out["loss_gap"] = math.inf
    return out


def feed_rows(batches: list[dict], seed: int, vocab: int, rows: int,
              ascent_rows: int) -> int:
    """`generator.unknown_rows` over the descent and ascent rows the check
    steps were fed, plus every row missing from or added to the `rows`
    descent and `ascent_rows` ascent rows each step should have."""
    fed, bad = [], 0
    for b in batches:
        fed.append(np.asarray(b["tokens"]))
        bad += abs(fed[-1].shape[0] - rows)
        asc = b.get("ascent")
        if asc is not None:
            fed.append(np.asarray(asc["tokens"]))
        bad += abs((0 if asc is None else fed[-1].shape[0]) - ascent_rows)
    # step k draws streams 2k (descent) and 2k + 1 (ascent) of its seed
    return bad + generator.unknown_rows(fed, seed, vocab,
                                      streams=2 * len(batches))


def verdict(nums: dict, limits: dict) -> tuple[bool, list[str]]:
    """(correct, one line per number: name, value, limit, verdict)."""
    ok, lines = True, []
    for name, limit in limits.items():
        value = nums.get(name, math.nan)
        good = math.isfinite(value) and value <= limit
        ok &= good
        lines.append(f"{name} {value!r} limit {limit!r} "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines
