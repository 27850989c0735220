"""Each driver runs a tiny cell in-process on the CPU, and the command
refuses to run without a TPU.  The tiny cells are added to a copy of the
benchmark by adding files only (see ``conftest.tiny_bench``)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchtools import REPO


def _run_cell(root, cell, capsys, seed=2_300_000_017):
    import importlib.util
    spec = importlib.util.spec_from_file_location("tiny_bench_run",
                                                  root / "bench/run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1",
              "--trace", "0"], root=root, require_tpu=False,
             compile_cache=False)
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), out, err


@pytest.mark.parametrize("cell,metric", [
    ("tiny_ru_sat", "rt_channels"),
    ("tiny_ru_flowcell", "rt_channels"),
    ("tiny_ru_paced", "decision_p95_ms"),
    ("tiny_offline", "offline_samples_per_s"),
])
def test_tiny_cell_result_line(tiny_root, capsys, cell, metric):
    res, out, err = _run_cell(tiny_root, cell, capsys)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["attempted"] > 0
    assert set(res["metrics"]) == {metric, "setup_s"}
    assert res["metrics"][metric]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    # the numbers compared are the last lines on standard error
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail)
    info = [line for line in out.splitlines() if line.startswith("info ")]
    assert json.loads(info[0][5:])["setup"]["setup_s"] > 0


def test_cli_exits_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ru_cnn460k_sat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_unknown_cell_is_refused(tiny_root):
    sys.path.insert(0, str(REPO))
    from bench import run
    with pytest.raises(SystemExit):
        run.load_cell(tiny_root, "no_such_cell")
