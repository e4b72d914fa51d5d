"""Each cell rehearsed on the CPU at OLMo's REDUCED sizes through the whole
run (`run.run_cell`, past the command's refusal of a CPU), and the check
shown to fail: with the control in the program's place, and with each fault
a one-chip training cell can have planted in the timed path."""
from __future__ import annotations

import functools
import json
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import calibrate, load, peaks, reference, run, system, weights

BENCH = load.benchmark()
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
FOUR_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
# src/repro/configs/olmo_1b.py REDUCED, at a short sequence
REDUCED = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
           "d_ff": 128, "vocab_size": 256, "remat": "none",
           "compute_dtype": "float32"}
SEED = 2**33 + 17     # a seed above 32 bits


def tiny(name: str, **over) -> tuple[dict, dict]:
    cell = {**load.workload(name), "seq": 64, **over}
    config = load.config(cell["config"])
    return cell, {**config, "model": {**config["model"], **REDUCED}}


def run_tiny(name, *, trace=False, system_cls=None, seed=SEED, **over):
    cell, config = tiny(name, **over)
    return run.run_cell(
        cell, config, seed, 0.5, trace, t_start=time.perf_counter(),
        devices=jax.devices()[:cell["chips"]],
        peaks=peaks.peaks("TPU v5 lite"), system_cls=system_cls)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_rehearsal_end_to_end(name):
    out = run_tiny(name)
    res = out["result"]
    assert res["correct"], out["lines"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in load.metrics_for(BENCH, "end_to_end", name)}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_rehearsal_traced():
    name = ONE_CHIP[0]
    res = run_tiny(name, trace=True)["result"]
    assert res["correct"]
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    # the CPU runs no Pallas kernel and reports no memory peak: those
    # readers find nothing and their metrics are left out
    assert {"loop_host_ms", "data_wait_ms", "idle_share", "mfu"} <= set(
        res["metrics"])
    assert "attn_fwd_roofline" not in res["metrics"]
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def _on_four_devices(code: str) -> dict:
    """Run `code` in a process that sees 4 virtual CPU devices; the JSON
    object on its last line of output."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    head = (f"import json, sys\nsys.path[:0] = [{str(load.ROOT)!r}, "
            f"{str(load.ROOT / 'src')!r}]\n")
    proc = subprocess.run([sys.executable, "-c", head + code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def four_chip_runs():
    """Each four-chip cell rehearsed whole on a (4, 1) mesh of virtual CPU
    devices at REDUCED widths, sound and with half of each descent batch
    left out inside the step, in one process."""
    return _on_four_devices(f"""
import functools
import jax
from bench import calibrate, system
from bench.tests.test_cells import run_tiny
out = {{"devices": jax.device_count()}}
for name in {FOUR_CHIP!r}:
    half = functools.partial(system.ProgramSystem,
                             alter_step=calibrate.half_batch)
    out[name] = {{fault: {{"correct": r["result"]["correct"],
                           "lines": r["lines"]}}
                 for fault, r in (("none", run_tiny(name)),
                                  ("half_batch", run_tiny(
                                      name, system_cls=half)))}}
print(json.dumps(out))
""")


@pytest.mark.parametrize("name", FOUR_CHIP)
def test_rehearsal_on_four_virtual_devices(four_chip_runs, name):
    """The harness's sharded path: the cell's per-leaf state sharded by the
    program's rules, checked against the reference placed on the same
    four devices."""
    assert four_chip_runs["devices"] == 4
    got = four_chip_runs[name]["none"]
    assert got["correct"], got["lines"]


@pytest.mark.parametrize("name", FOUR_CHIP)
def test_half_batch_on_four_virtual_devices_is_not_correct(four_chip_runs,
                                                           name):
    got = four_chip_runs[name]["half_batch"]
    assert not got["correct"], got["lines"]


def test_mesh_placed_reference_matches_one_device():
    """The reference on a (4, 1) mesh reads what it reads on one device, to
    within float32 reduction order, and no leaf of `check.SPLIT_LEAF`
    elements or more sits whole on one device: not the seed's weights, not
    the reference's state, not the batch's rows."""
    got = _on_four_devices(f"""
import jax
import numpy as np
from bench import check, generator, load, system
arch = load.arch("olmo")
# embed 4096 x 256 and the SwiGLU stacks 4 x 256 x 1024 are 2**20
# elements; the attention stacks 4 x 256 x 256 are not
dims = {{**{REDUCED!r}, "n_layers": 4, "d_model": 256, "d_ff": 1024,
        "vocab_size": 4096, "norm_eps": 1e-6, "rope_theta": 1e4,
        "tie_embeddings": True}}
cell = load.workload({FOUR_CHIP[0]!r})
train = {{**cell["train"], "method": cell["method"]}}
batches = []
for k in range(3):
    tok = generator.rows(5, 4096, 8, 64, 2 * k)
    asc = generator.rows(5, 4096, 4, 64, 2 * k + 1)
    batches.append({{"tokens": tok, "labels": generator.labels_of(tok),
                    "ascent": {{"tokens": asc,
                               "labels": generator.labels_of(asc)}}}})
out = {{}}
for shape in ([1, 1], [4, 1]):
    mesh = system.make_mesh(shape)
    params0 = check.seed_params(arch, dims, 5, mesh)
    out[str(shape)] = check.reference_readings(arch, dims, train, params0,
                                               batches, mesh)
    _, st_sh = check._reference_step(arch, dims, train, "fp32", mesh,
                                     params0)
    placed = [jax.tree.map(lambda x: x.sharding, params0), st_sh.params,
              st_sh.mu, st_sh.nu, st_sh.ascent]
    pairs = [(x, sh) for tree in placed
             for x, sh in zip(jax.tree.leaves(params0), jax.tree.leaves(tree))
             if x.size >= check.SPLIT_LEAF]
    pairs += zip(jax.tree.leaves(batches[0]),
                 jax.tree.leaves(check.row_placement(batches[0], mesh)))
    out[str(shape) + " split"] = [(x.shape, sh.shard_shape(x.shape))
                                  for x, sh in pairs]
print(json.dumps(out))
""")
    one, four = got["[1, 1]"], got["[4, 1]"]
    # float32 reduction order: the two read within 2.3e-07 of each other
    for key in ("loss", "g1", "change"):
        np.testing.assert_allclose(four[key], one[key], rtol=1e-6)
    split = got["[4, 1] split"]
    # 4 leaves of 2**20 in the weights and the state's 4 trees; 4 row arrays
    assert len(split) == 4 * 5 + 4
    for shape, shard in split:
        assert 4 * math.prod(shard) == math.prod(shape), (shape, shard)
    assert all(shard == shape for shape, shard in got["[1, 1] split"])


def test_the_command_refuses_a_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(load.BENCH / "run.py"), "--workload",
         ONE_CHIP[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


# --- the check fails -----------------------------------------------------

class StateUnchanged(system.ProgramSystem):
    """Every step returns the state it was given: parameters, optimizer and
    method state; only its step counter and key advance (else the loop
    would never reach its step count)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        inner = self.executor.inner

        def step(state, batch):
            new, metrics = inner.step(jax.tree.map(jnp.copy, state), batch)
            return state._replace(step=new.step, rng=new.rng), metrics

        self.executor.inner = type("Stuck", (), {
            "step": staticmethod(step), "close": inner.close})()


def _alter_one_token(batch):
    tokens = np.array(batch["tokens"])
    tokens[0, 5] = (tokens[0, 5] + 1) % 256
    return {**batch, "tokens": jnp.asarray(tokens)}


class Control(system.ProgramSystem):
    """The reference at float8 put in the program's place: its three steps
    on the batches the feed gave are what set-up reports."""

    def set_up(self):
        r = super().set_up()
        arch = load.arch(self.config["arch"])
        dims = system.model_dims(self.config)
        train = {**self.cell["train"], "method": self.cell["method"]}
        st = reference.init_state(weights.make_params(self.seed, arch, dims))
        step = jax.jit(reference.make_step(arch.loss, dims, train, "fp8"))
        losses, g1 = [], []
        for k, batch in enumerate(r["batches"]):
            st, loss, g = step(st, jax.tree.map(jnp.asarray, batch))
            losses.append(float(loss))
            if k == 0:
                g1 = [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(g)]
        return {**r, "loss": losses, "g1": g1,
                "params3": jax.tree.leaves(jax.device_get(st.params))}


FAULTS = {
    "control_fp8": Control,
    "state_unchanged": StateUnchanged,
    "half_batch": functools.partial(system.ProgramSystem,
                                    alter_step=calibrate.half_batch),
    "token_altered": functools.partial(system.ProgramSystem,
                                       alter_feed=_alter_one_token),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", ONE_CHIP)
def test_a_broken_timed_path_is_not_correct(name, fault):
    out = run_tiny(name, system_cls=FAULTS[fault])
    assert not out["result"]["correct"], out["lines"]
    failed = {k for k, v in out["result"]["checks"].items()
              if not v["value"] <= v["limit"]}
    if fault == "token_altered":
        assert "feed_rows" in failed
    if fault == "state_unchanged":
        assert {"grad_gap", "change_gap"} <= failed
