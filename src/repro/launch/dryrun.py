import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs abstract TrainState / cache / batch stand-ins
     (ShapeDtypeStruct; no device allocation),
  3. jit-lowers the AsyncSAM train_step (train shapes) or the serve step
     (prefill/decode shapes) with explicit in/out shardings,
  4. compiles, prints memory_analysis() and cost_analysis(),
  5. extracts the collective-op inventory from the optimized HLO, and
  6. writes a JSON artifact consumed by benchmarks/roofline.py and
     EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--quick]
"""
import argparse
import dataclasses
import json
import pathlib
import re
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.core import MethodConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_spec_tree, cache_spec_tree,
                                   state_spec_tree, to_named)
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import (SHAPES, batch_spec, build_model, decode_batch_spec,
                          shape_applicable)
from repro.models.config import ModelConfig, ShapeSpec
from repro.optim import make_optimizer
from repro.utils import trees

ARTIFACT_DIR = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# hardware constants (TPU v5e-class target; see EXPERIMENTS.md §Roofline)
PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link


def input_specs(arch: str, shape_name: str = "train_4k",
                method_cfg: Optional[MethodConfig] = None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the given cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    method_cfg = method_cfg or MethodConfig()
    if shape.kind == "train":
        return batch_spec(cfg, shape, ascent_fraction=method_cfg.ascent_fraction)
    if shape.kind == "prefill":
        return batch_spec(cfg, shape)
    return decode_batch_spec(cfg, shape)


def _abstract_cache(cfg: ModelConfig, shape: ShapeSpec):
    from repro.models.registry import build_model as _bm

    bundle = _bm(cfg)
    return jax.eval_shape(
        lambda: bundle.init_cache(shape.global_batch, shape.seq_len,
                                  pos=shape.seq_len - 1))


# ---------------------------------------------------------------------------
# HLO collective inventory
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s32|u32|s8|u8|pred|s64|u64)\[([\d,]*)\]")
_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s8": 1, "u8": 1, "pred": 1}
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s*(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")
_GROUP_RE = re.compile(r"replica_groups=\{([^}]*(?:\},\{[^}]*)*)\}|"
                       r"replica_groups=\[(\d+),(\d+)\]")


def _result_bytes(line: str) -> int:
    """Sum byte sizes of all result shapes on an HLO instruction line."""
    lhs = line.split("=", 1)[0] + "=" + line.split("=", 1)[1].split("(", 1)[0]
    total = 0
    for m in _SHAPE_RE.finditer(lhs):
        dims = m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[m.group(1)]
    return total


def _group_size(line: str) -> int:
    m = _GROUP_RE.search(line)
    if not m:
        return 1
    if m.group(2) is not None:          # iota format [g,n]<=[...]
        return int(m.group(3))
    first = m.group(1).split("}", 1)[0]
    return max(1, first.count(",") + 1)


def collective_inventory(hlo_text: str) -> list[dict]:
    """One record per collective op: kind, result bytes, group size."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done(" in line:    # count start ops once
            continue
        out.append({"kind": m.group(1), "bytes": _result_bytes(line),
                    "group": _group_size(line)})
    return out


def collective_cost_bytes(inventory: list[dict]) -> float:
    """Per-chip bytes-on-the-wire estimate (ring algorithms; DESIGN.md §5)."""
    total = 0.0
    for rec in inventory:
        b, n = rec["bytes"], max(2, rec["group"])
        ring = (n - 1) / n
        if rec["kind"] == "all-reduce":
            total += 2 * b * ring
        elif rec["kind"] == "all-gather":
            total += b * ring                      # result-sized, gathered in
        elif rec["kind"] == "reduce-scatter":
            total += b * (n - 1)                   # operand = result * n
        elif rec["kind"] == "all-to-all":
            total += b * ring
        else:                                      # collective-permute
            total += b
    return total


# ---------------------------------------------------------------------------
# One-cell dry-run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    status: str                  # ok | skipped | failed
    note: str = ""
    lower_s: float = 0.0
    compile_s: float = 0.0
    flops: float = 0.0           # per-device HLO flops
    bytes_accessed: float = 0.0  # per-device HLO bytes
    collective_bytes: float = 0.0
    peak_memory_per_device: float = 0.0
    n_collectives: int = 0
    output_bytes: float = 0.0
    argument_bytes: float = 0.0
    param_count: int = 0         # parameter elements (train cells)
    param_bytes: int = 0         # parameter tree bytes (train cells)
    inventory: list = dataclasses.field(default_factory=list)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             method: str = "async_sam", method_cfg: Optional[MethodConfig] = None,
             save: bool = True, verbose: bool = True,
             cfg_override: Optional[ModelConfig] = None,
             tag: str = "") -> CellResult:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    result = CellResult(arch=arch, shape=shape_name, mesh=mesh_name, status="ok",
                        note=tag)

    ok, why = shape_applicable(cfg, shape)
    if not ok:
        result.status, result.note = "skipped", why
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: SKIP ({why})")
        if save:
            _save(result, tag)
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    bundle = build_model(cfg)
    # default execution profile: AsyncSAM with b'/b=25% and 4 microbatches
    mcfg = method_cfg or MethodConfig(name=method, n_microbatches=4)

    from repro.models.partitioning import activation_sharding

    t0 = time.time()
    try:
        with jax.set_mesh(mesh), activation_sharding(mesh):
            if shape.kind == "train":
                # the Engine's executor owns the jit/sharding plumbing here
                # (the same path launch/train.py drives), not a local shim
                from repro.engine import FusedExecutor
                executor = FusedExecutor(bundle.loss_fn, mcfg,
                                         make_optimizer("adamw", 1e-3,
                                                        clip_norm=1.0),
                                         mesh=mesh, model_cfg=cfg)
                state_sds = executor.abstract_state(
                    lambda: bundle.init(jax.random.PRNGKey(0)),
                    jax.random.PRNGKey(1))
                batch_sds = batch_spec(cfg, shape,
                                       ascent_fraction=mcfg.ascent_fraction)
                result.param_count = trees.tree_size(state_sds.params)
                result.param_bytes = trees.tree_bytes(state_sds.params)
                lowered = executor.lower(state_sds, batch_sds)
            elif shape.kind == "prefill":
                step = make_prefill_step(bundle)
                params_sds = jax.eval_shape(
                    lambda: bundle.init(jax.random.PRNGKey(0)))
                batch_sds = batch_spec(cfg, shape)
                params_sh = to_named(state_spec_tree(params_sds, cfg, mesh), mesh)
                batch_sh = to_named(batch_spec_tree(batch_sds, mesh), mesh)
                cache_sds = jax.eval_shape(step, params_sds, batch_sds)[1]
                cache_sh = to_named(cache_spec_tree(cache_sds, cfg, mesh), mesh)
                jitted = jax.jit(step, in_shardings=(params_sh, batch_sh),
                                 out_shardings=(None, cache_sh))
                lowered = jitted.lower(params_sds, batch_sds)
            else:  # decode
                step = make_decode_step(bundle)
                params_sds = jax.eval_shape(
                    lambda: bundle.init(jax.random.PRNGKey(0)))
                cache_sds = _abstract_cache(cfg, shape)
                batch_sds = decode_batch_spec(cfg, shape)
                params_sh = to_named(state_spec_tree(params_sds, cfg, mesh), mesh)
                cache_sh = to_named(cache_spec_tree(cache_sds, cfg, mesh), mesh)
                batch_sh = to_named(batch_spec_tree(batch_sds, mesh), mesh)
                jitted = jax.jit(step, in_shardings=(params_sh, cache_sh, batch_sh),
                                 out_shardings=(None, cache_sh),
                                 donate_argnums=(1,))
                lowered = jitted.lower(params_sds, cache_sds, batch_sds)
            result.lower_s = time.time() - t0

            t1 = time.time()
            compiled = lowered.compile()
            result.compile_s = time.time() - t1

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis() or {}
            result.flops = float(cost.get("flops", 0.0))
            result.bytes_accessed = float(cost.get("bytes accessed", 0.0))
            if mem is not None:
                result.peak_memory_per_device = float(
                    getattr(mem, "temp_size_in_bytes", 0)
                    + getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "output_size_in_bytes", 0)
                    - getattr(mem, "alias_size_in_bytes", 0))
                result.argument_bytes = float(getattr(mem, "argument_size_in_bytes", 0))
                result.output_bytes = float(getattr(mem, "output_size_in_bytes", 0))
            hlo = compiled.as_text()
            inv = collective_inventory(hlo)
            result.n_collectives = len(inv)
            result.collective_bytes = collective_cost_bytes(inv)
            # keep a compact inventory (top ops by bytes)
            agg: dict[str, list[float]] = {}
            for rec in inv:
                a = agg.setdefault(rec["kind"], [0, 0.0])
                a[0] += 1
                a[1] += rec["bytes"]
            result.inventory = [
                {"kind": k, "count": v[0], "result_bytes": v[1]}
                for k, v in sorted(agg.items())]

            if verbose:
                print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
                      f"(lower {result.lower_s:.1f}s, compile {result.compile_s:.1f}s)")
                print("  memory_analysis:", mem)
                print(f"  cost_analysis: flops={result.flops:.3e} "
                      f"bytes={result.bytes_accessed:.3e}")
                print(f"  collectives: n={result.n_collectives} "
                      f"wire_bytes/chip={result.collective_bytes:.3e}")
    except Exception as e:  # noqa: BLE001 — a failing cell is a recorded bug
        result.status = "failed"
        result.note = f"{type(e).__name__}: {e}"[:500]
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: FAILED {result.note}")

    if save:
        _save(result, tag)
    return result


def _save(result: CellResult, tag: str = "") -> None:
    ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    path = ARTIFACT_DIR / f"{result.arch}_{result.shape}_{result.mesh}{suffix}.json"
    path.write_text(json.dumps(result.to_json(), indent=1))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--method", default="async_sam")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        cells = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            r = run_cell(arch, shape, multi_pod=mp, method=args.method,
                         tag=args.tag)
            failures += r.status == "failed"
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
