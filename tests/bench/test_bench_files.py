"""Every file the benchmark names exists and loads, and BENCHMARK.json
keeps the shape the harness reads."""
from __future__ import annotations

import json
import re

import pytest

from benchtools import REPO

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _load(path):
    import importlib.util
    spec = importlib.util.spec_from_file_location("m_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51


def check_config_file(root, data: dict):
    """A configuration against its own model family's module: its count
    of parameters, its weights kind and control, and drivers that exist."""
    from bench.configs import model_of
    model = model_of(data)
    assert model.n_params(data) == data["params"]
    drivers = {p.stem for p in (root / "bench/drivers").glob("*.py")}
    assert set(data["limits"]) <= drivers
    assert data["weights"]["kind"] in model.WEIGHT_KINDS
    assert data["control"] in model.CONTROLS


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_loads(cfg):
    assert NAME.match(cfg["name"])
    check_config_file(REPO, json.loads((REPO / cfg["file"]).read_text()))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_names_existing_files(cell):
    assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    traffic = json.loads(
        (REPO / "bench/traffic" / f"{cell['traffic']}.json").read_text())
    assert (REPO / "bench/drivers" / f"{traffic['driver']}.py").is_file()
    assert len(traffic["why"]) <= 200 and len(cell["why"]) <= 200
    cfg = json.loads((REPO / next(c["file"] for c in BENCH["configs"]
                                  if c["name"] == cell["config"]))
                     .read_text())
    assert traffic["driver"] in cfg["limits"]


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_a_reader(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    from bench.run import reader_path
    reader = _load(reader_path(REPO, metric["name"]))
    assert callable(reader.read)
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if metric in BENCH["per_layer"]:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("name,reader", [("mfu.ru", "mfu.py"),
                                         ("device_idle.new_cell",
                                          "device_idle.py"),
                                         ("setup_s", "setup_s.py")])
def test_a_metric_finds_its_reader_by_name(name, reader):
    """A later cell's ``<metric>.<cell>`` needs no file of its own."""
    from bench.run import reader_path
    assert reader_path(REPO, name) == REPO / "bench/metrics" / reader


def test_every_cell_reports_enough():
    for cell in BENCH["workloads"]:
        def applies(m):
            return cell["name"] in m.get("workloads", [cell["name"]])
        e2e = [m["name"] for m in BENCH["end_to_end"] if applies(m)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        assert any(applies(m) for m in BENCH["per_layer"]), cell["name"]


def test_layers_named_once_each():
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
