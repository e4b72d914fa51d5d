"""The benchmark is driven by data: every cell, configuration and metric in
BENCHMARK.json resolves to a file of its own, found by its name."""
from __future__ import annotations

import json
import math
import re
import shutil

import pytest

from bench import load

BENCH = load.benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_shape():
    assert set(BENCH) == KEYS
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units_use_only_allowed_characters():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.fullmatch(entry["name"]), entry["name"]
            names.append((group, entry["name"]))
            if "unit" in entry:
                assert UNIT.fullmatch(entry["unit"]), entry["unit"]
            for text in (entry.get("why"), entry.get("layer"),
                         entry.get("source")):
                if text is not None:
                    assert 1 <= len(text) <= 200 and "\n" not in text
    for w in BENCH["workloads"]:
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
    for c in BENCH["configs"]:
        assert all(NAME.fullmatch(k) for k in c["reduced"])
    assert len(set(names)) == len(names)
    metric_names = [m["name"] for g in ("end_to_end", "per_layer")
                    for m in BENCH[g]]
    assert len(set(metric_names)) == len(metric_names)


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_workload_resolves_to_its_files(entry):
    cell = load.workload(entry["name"])
    for key in ("config", "traffic", "chips", "why"):
        assert cell[key] == entry[key], key
    assert math.prod(cell["mesh"]) == cell["chips"]
    for key in ("method", "batch", "seq", "ascent_fraction", "train"):
        assert key in cell, key     # from bench/traffic/<traffic>.json
    config = load.config(entry["config"])
    assert config["name"] == entry["config"]
    assert set(cell["limits"]) == {"loss_gap", "grad_gap", "change_gap",
                                   "feed_rows"}
    assert cell["limits"]["feed_rows"] == 0


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_config_file_states_its_cut(entry):
    path = load.ROOT / entry["file"]
    assert path.parent == load.BENCH / "configs"
    config = json.loads(path.read_text())
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    changed = [k for k, v in config["published"].items()
               if config["model"][k] != v]
    assert changed == entry["reduced"]
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


ARCH_API = ("init_params", "loss", "matmul_params", "param_count",
            "train_flops_per_token")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_config_names_an_arch_that_resolves(entry):
    arch = load.arch(load.config(entry["name"])["arch"])
    assert all(callable(getattr(arch, f)) for f in ARCH_API)
    assert load.arch(load.config(entry["name"])["arch"]) is arch


def test_an_added_arch_file_is_found_with_no_other_edit(tmp_path):
    (tmp_path / "archs").mkdir()
    (tmp_path / "archs" / "toy.py").write_text(
        "def init_params(key, dims):\n    return {}\n"
        "def loss(params, batch, dims, precision='fp32'):\n    return 0.0\n"
        "def matmul_params(dims):\n    return dims['width'] ** 2\n"
        "def param_count(dims):\n    return dims['width'] ** 2\n"
        "def train_flops_per_token(dims, seq):\n"
        "    return 6 * matmul_params(dims)\n")
    toy = load.arch("toy", tmp_path)
    assert all(callable(getattr(toy, f)) for f in ARCH_API)
    assert toy.train_flops_per_token({"width": 3}, 8) == 54
    with pytest.raises(FileNotFoundError):
        load.arch("absent", tmp_path)


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_metric_resolves_to_a_reader(entry):
    assert callable(load.reader(entry["name"]))
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert entry["layer"] in layers
    for cell in entry.get("workloads", []):
        assert cell in {w["name"] for w in BENCH["workloads"]}


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in load.metrics_for(BENCH, "end_to_end",
                                                  w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert load.metrics_for(BENCH, "per_layer", w["name"])


def test_bounds_within_the_contract():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    fours = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert fours <= max(1, len(BENCH["workloads"]) // 2)


def test_an_added_workload_file_is_found_with_no_other_edit(tmp_path):
    bench = tmp_path / "bench"
    for kind in ("workloads", "traffic", "configs"):
        shutil.copytree(load.BENCH / kind, bench / kind)
    traffic = json.loads((bench / "traffic" / "sgd.b2.s2048.json").read_text())
    (bench / "traffic" / "sgd.b4.s1024.json").write_text(json.dumps(
        {**traffic, "name": "sgd.b4.s1024", "batch": 4, "seq": 1024}))
    cell = json.loads((bench / "workloads" / "olmo-1b-3l.sgd.json").read_text())
    new = {**cell, "name": "olmo-1b-3l.sgd.s1024", "traffic": "sgd.b4.s1024"}
    (bench / "workloads" / "olmo-1b-3l.sgd.s1024.json").write_text(
        json.dumps(new))
    got = load.workload("olmo-1b-3l.sgd.s1024", bench)
    assert (got["seq"], got["batch"], got["method"]) == (1024, 4, "sgd")
    assert got["name"] == "olmo-1b-3l.sgd.s1024"
    assert load.config(got["config"], bench)["name"] == "olmo-1b-3l"
    bench_json = {**BENCH, "workloads": BENCH["workloads"] + [
        {"name": new["name"], "config": new["config"],
         "traffic": new["traffic"], "chips": 1, "why": "test"}]}
    assert load.metrics_for(bench_json, "per_layer", new["name"])


def test_an_added_metric_reader_is_found(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")

    class Ctx:
        steps = 7
    assert load.reader("steps_seen", tmp_path)(Ctx()) == 7.0


def test_a_bad_name_is_refused():
    with pytest.raises(ValueError):
        load.workload("../BENCHMARK")
