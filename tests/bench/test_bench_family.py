"""A second model family enters the offline driver's cells by new files
alone: a configuration with none of the conv stack's keys, its module under
``bench/configs/``, a traffic file and entries appended to
``BENCHMARK.json``, added to a tiny copy of the benchmark and run through
``bench/run.py`` on the CPU.  The family's files are kept under
``tests/bench/family``; it is never a cell of the benchmark itself."""
from __future__ import annotations

import importlib
import json
import shutil
import sys

import numpy as np
import pytest

from benchtools import REPO
from test_bench_drivers import _run_cell
from test_bench_files import check_config_file

FAMILY = REPO / "tests" / "bench" / "family"
CELL = "tiny_offline_convlist"
CONV_KEYS = {"kernels", "strides", "channels", "in_channels"}


def _bench_modules():
    return [m for m in sys.modules if m == "bench" or m.startswith("bench.")]


@pytest.fixture
def family_root(tiny_root, monkeypatch):
    """The tiny copy with the family's files added, and ``bench`` imported
    from that copy for the length of the test, as ``bench/run.py`` imports
    it from its own checkout."""
    shutil.copy(FAMILY / "convlist.py", tiny_root / "bench" / "configs")
    shutil.copy(FAMILY / "convlist.json", tiny_root / "bench" / "configs")
    shutil.copy(FAMILY / f"{CELL}.json", tiny_root / "bench" / "traffic")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "convlist", "source": "test",
                             "file": "bench/configs/convlist.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "convlist",
                               "traffic": CELL, "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "offline_samples_per_s":
            m["workloads"].append(CELL)
    for name, source, layer in (
            ("mfu", "host_clock", "device"),
            ("forward_roofline", "device_trace", "basecaller forward")):
        bench["per_layer"].append(
            {"name": f"{name}.{CELL}", "unit": "%", "better": "higher",
             "source": source, "layer": layer,
             "moves": "offline_samples_per_s", "workloads": [CELL]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    for name in _bench_modules():
        monkeypatch.delitem(sys.modules, name)
    monkeypatch.syspath_prepend(str(tiny_root))
    yield tiny_root
    for name in _bench_modules():
        del sys.modules[name]


def test_the_family_passes_the_config_file_checks(family_root):
    data = json.loads((family_root / "bench/configs/convlist.json")
                      .read_text())
    assert not CONV_KEYS & set(data)
    check_config_file(family_root, data)
    from bench.configs import model_of
    assert model_of(data).__file__ == str(
        family_root / "bench" / "configs" / "convlist.py")


def test_the_family_edits_no_file_of_the_benchmark(family_root):
    for path in (REPO / "bench").rglob("*"):
        if path.is_file() and not {"__pycache__", "fixtures"} & set(
                path.parts):
            copy = family_root / path.relative_to(REPO)
            assert copy.read_bytes() == path.read_bytes(), path


def test_the_family_runs_correct_through_the_harness(family_root, capsys):
    res, _, _ = _run_cell(family_root, CELL, capsys)
    assert res["correct"] is True and res["attempted"] > 0
    assert set(res["metrics"]) == {"offline_samples_per_s", "setup_s"}
    assert res["metrics"]["offline_samples_per_s"]["value"] > 0
    assert res["checks"]["token_mismatch"]["value"] <= \
        res["checks"]["token_mismatch"]["limit"]


def test_the_family_traced_run_reads_mfu(family_root, capsys, monkeypatch):
    """The traced run reads ``mfu.<cell>`` through the family's
    ``macs_per_sample``.  The CPU has no row in the table of peaks, so it
    borrows the v5e's; its trace holds no TPU plane, so the forward's
    roofline finds nothing to read and is left out."""
    from bench.lib import cost
    real = cost.peaks
    monkeypatch.setattr(cost, "peaks", lambda kind: real(
        "TPU v5 lite" if kind == "cpu" else kind))
    run = importlib.import_module("bench.run")
    res = run.main(["--workload", CELL, "--seed", "2300000029",
                    "--seconds", "1", "--trace", "1"], root=family_root,
                   require_tpu=False, compile_cache=False)
    capsys.readouterr()
    assert res["correct"] is True
    assert set(res["metrics"]) == {f"mfu.{CELL}"}
    assert res["metrics"][f"mfu.{CELL}"]["value"] > 0


def test_the_family_with_perturbed_weights_is_not_correct(family_root,
                                                           capsys,
                                                           monkeypatch):
    """The engine gets the weights with the head's base columns rolled, so
    every base it calls is another: the check must see it."""
    model = importlib.import_module("bench.configs.convlist")
    build = model.offline_engine

    def perturbed(params, cfg, **kw):
        head = f"conv{len(cfg['layers'])}"
        roll = np.array([0, 2, 3, 4, 1])
        params = {**params, head: {"w": params[head]["w"][..., roll],
                                   "b": params[head]["b"][roll]}}
        return build(params, cfg, **kw)

    monkeypatch.setattr(model, "offline_engine", perturbed)
    res, _, _ = _run_cell(family_root, CELL, capsys)
    assert res["correct"] is False
    assert res["checks"]["token_mismatch"]["value"] > \
        res["checks"]["token_mismatch"]["limit"]
