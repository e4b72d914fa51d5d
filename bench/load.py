"""Finds a cell's files by the names in `BENCHMARK.json`.

A configuration is `bench/configs/<config>.json`, a traffic mix (the
method, batch, sequence length, ascent share and optimizer settings a step
is fed) `bench/traffic/<traffic>.json`, a cell (its configuration, traffic,
chips, mesh and the limits of its check) `bench/workloads/<cell>.json`, and
a per-layer metric a reader `bench/metrics/<metric>.py` with a function
`read(ctx) -> float | None`, and an architecture (a configuration's "arch":
its weights, plain reference and FLOP count) `bench/archs/<arch>.py`.
Adding any of them is adding a file and an entry; no file that is there
changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
import re
from types import ModuleType
from typing import Callable

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def _name(kind: str, name: str) -> str:
    if not NAME.fullmatch(name):
        raise ValueError(f"{kind} name {name!r} is not a benchmark name")
    return name


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _named(kind: str, name: str, bench: pathlib.Path) -> dict:
    path = bench / kind / f"{_name(kind, name)}.json"
    entry = json.loads(path.read_text())
    if entry["name"] != name:
        raise ValueError(f"{path} names itself {entry['name']!r}")
    return entry


def workload(name: str, bench: pathlib.Path = BENCH) -> dict:
    """A cell's file, with its traffic mix's parameters merged in."""
    cell = _named("workloads", name, bench)
    traffic = _named("traffic", cell["traffic"], bench)
    return {**{k: v for k, v in traffic.items() if k != "name"}, **cell}


def config(name: str, bench: pathlib.Path = BENCH) -> dict:
    return _named("configs", name, bench)


def _module(kind: str, name: str, bench: pathlib.Path) -> ModuleType:
    path = bench / kind / f"{_name(kind, name)}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader(metric: str, bench: pathlib.Path = BENCH) -> Callable:
    """The `read` function of `bench/metrics/<metric>.py`."""
    return _module("metrics", metric, bench).read


@functools.cache
def arch(name: str, bench: pathlib.Path = BENCH) -> ModuleType:
    """`bench/archs/<name>.py`: `init_params(key, dims)`, `loss(params,
    batch, dims, precision)`, `matmul_params(dims)`, `param_count(dims)`
    and `train_flops_per_token(dims, seq)`. One module object per file in a
    process, so that the reference's jitted steps are traced once."""
    return _module("archs", name, bench)


def metrics_for(bench_json: dict, kind: str, cell: str) -> list[dict]:
    """The `kind` ("end_to_end" or "per_layer") metrics a cell reports:
    those that list it, and those that list no cells."""
    return [m for m in bench_json[kind]
            if cell in m.get("workloads", [cell])]
