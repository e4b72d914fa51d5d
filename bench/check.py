"""How `correct` is decided for a training cell.

The program's readings come from its own first three steps in set-up
(`system.ProgramSystem.set_up`); the reference follows the same three steps
from the same seed's weights on the batches the program was fed. The
reference is the method's step (`reference.py`) over the configuration's
architecture (`bench/archs/<arch>.py`, found by the configuration's "arch"),
and runs on the cell's own chips, placed by this benchmark's rule
(`placement`), never the program's. The numbers compared, each against its
cell's limit:

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
from types import ModuleType

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import generator, load, reference, system, weights

NEGLIGIBLE_GRAD = 1e-3   # of the median leaf's first-gradient norm
SPLIT_LEAF = 2**20       # elements from which a leaf is split over the chips


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]


@jax.jit
def _change_norms(new, old):
    return [jnp.sqrt(jnp.sum(jnp.square(a - b)))
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]


def placement(shapes, mesh):
    """Shardings on `mesh` for a tree of shapes, by the benchmark's own rule:
    a leaf of at least `SPLIT_LEAF` elements is split over all the mesh's
    chips on its largest dimension that the chip count divides (the first
    of equals), else replicated; smaller leaves are replicated. On one chip
    every leaf is that chip's.

    The largest dimension, and not the first: a stack of layers split on
    its layer axis leaves each layer whole on one chip, and the compiler
    then keeps every layer's gathered copy for the backward pass."""
    every = tuple(mesh.axis_names)

    def one(x):
        fits = [i for i, d in enumerate(x.shape) if d % mesh.size == 0]
        if math.prod(x.shape) >= SPLIT_LEAF and fits:
            i = max(fits, key=lambda i: (x.shape[i], -i))
            return NamedSharding(mesh, P(*(None,) * i, every))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, shapes)


def row_placement(batch, mesh):
    """Shardings that split each batch leaf's rows over the mesh's chips
    (replicated where the chip count does not divide the rows)."""
    every = tuple(mesh.axis_names)
    return jax.tree.map(lambda x: NamedSharding(
        mesh, P(every) if x.shape[0] % mesh.size == 0 else P()), batch)


def seed_params(arch: ModuleType, dims: dict, seed: int, mesh) -> dict:
    """The seed's weights, placed on `mesh` by `placement`."""
    shapes = jax.eval_shape(lambda: arch.init_params(jax.random.PRNGKey(0),
                                                     dims))
    return weights.make_params(seed, arch, dims, placement(shapes, mesh))


_STEPS: dict = {}


def _reference_step(arch: ModuleType, dims: dict, train: dict,
                    precision: str, mesh, params0):
    """The jitted reference step and its state's shardings, one per
    (architecture, widths, training, precision, mesh) in a process, so that
    a process checking many seeds traces it once."""
    key = (json.dumps([arch.__name__, dims, train, precision],
                      sort_keys=True), mesh)
    if key not in _STEPS:
        p_sh = placement(params0, mesh)
        one = NamedSharding(mesh, P())
        st_sh = reference.State(p_sh, p_sh, p_sh, one, p_sh, one)
        step = jax.jit(reference.make_step(arch.loss, dims, train, precision),
                       donate_argnums=0, out_shardings=(st_sh, one, p_sh))
        _STEPS[key] = step, st_sh
    return _STEPS[key]


def reference_readings(arch: ModuleType, dims: dict, train: dict,
                       params0: dict, batches: list, mesh,
                       precision: str = "fp32", steps: int = 3) -> dict:
    """The reference's readings over `steps` steps from `params0` (the
    seed's weights as `seed_params` places them): each step's loss, each
    leaf's first clipped gradient norm, and each leaf's change norm over the
    steps. State and batches lie on `mesh` by `placement` and
    `row_placement`."""
    step, st_sh = _reference_step(arch, dims, train, precision, mesh,
                                  params0)
    st = jax.jit(lambda p: reference.init_state(jax.tree.map(jnp.copy, p)),
                 out_shardings=st_sh)(params0)
    losses, g1 = [], []
    for k, batch in enumerate(batches[:steps]):
        batch = jax.device_put(batch, row_placement(batch, mesh))
        st, value, g = step(st, batch)
        losses.append(float(value))
        if k == 0:
            g1 = [float(x) for x in _leaf_norms(g)]
        del g
    change = [float(x) for x in _change_norms(st.params, params0)]
    return {"loss": losses, "g1": g1, "change": change}


def program_change(params3: list, params0: dict) -> list[float]:
    """Each leaf's change norm from `params0` (the seed's weights) to the
    program's parameters after the check steps (host leaves, program leaf
    order)."""
    params0 = jax.tree.leaves(jax.device_get(params0))
    return [float(np.linalg.norm((np.asarray(a, np.float32) - b).ravel()))
            for a, b in zip(params3, params0)]


def program_numbers(cell: dict, config: dict, seed: int,
                    readings: dict) -> dict:
    """The compared numbers for the program's set-up `readings` (what
    `ProgramSystem.set_up` returns), against the reference on the cell's
    mesh. Run once the program's state is freed."""
    arch = load.arch(config["arch"])
    dims = system.model_dims(config)
    mesh = system.make_mesh(cell["mesh"])
    params0 = seed_params(arch, dims, seed, mesh)
    ref = reference_readings(arch, dims, {**cell["train"],
                                          "method": cell["method"]},
                             params0, readings["batches"], mesh)
    got = {"loss": readings["loss"], "g1": readings["g1"],
           "change": program_change(readings["params3"], params0),
           "feed_rows": feed_rows(readings["batches"], seed,
                                  dims["vocab_size"], cell["batch"],
                                  system.ascent_rows(cell))}
    return numbers(got, ref)


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
