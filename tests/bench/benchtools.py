"""A tiny copy of the benchmark for runs on the CPU.

``tiny_bench(tmp_path)`` copies ``bench/`` next to a ``BENCHMARK.json`` of
small cells over small traffic files (and a paced cell with its own
end-to-end metric), which is also how a later change adds
a cell: by adding files.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_RU = {
    "channels": 16, "pool": 64, "warm_ticks": 6, "check_reads": 24,
    "read_len": [60, 120], "genome_len": 4000, "start_spread_samples": 256,
    "panel": {"targets": 4, "target_len": 250},
}
TINY_OFFLINE = {"batch": 4, "chunk": 2000, "overlap": 100,
                "median_bases": 300, "sigma": 0.5, "min_bases": 100,
                "max_bases": 600, "pool_rows": 8, "check_rows": 6}
TINY_CNN = {"name": "cnn_tiny", "kernels": [5, 3, 1], "strides": [1, 2, 1],
            "channels": [8, 8, 5], "params": 289}


def tiny_bench(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-like directory holding ``bench/`` and a BENCHMARK.json of
    three tiny cells; returns its root."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    traffic = tmp / "bench" / "traffic"
    for name, base, extra in (
            ("ru_saturate", "ru_saturate", TINY_RU),
            ("ru_flowcell", "ru_flowcell", TINY_RU),
            ("ru_paced", "ru_flowcell", {**TINY_RU, "pacing": "paced"}),
            ("offline_long", "offline_long", TINY_OFFLINE)):
        t = json.loads((traffic / f"{base}.json").read_text())
        t.update(extra)
        (traffic / f"tiny_{name}.json").write_text(json.dumps(t))
    cnn = json.loads((tmp / "bench/configs/cnn460k.json").read_text())
    cnn.update(TINY_CNN)
    (tmp / "bench/configs/cnn_tiny.json").write_text(json.dumps(cnn))
    bench["configs"].append({"name": "cnn_tiny", "source": "test",
                             "file": "bench/configs/cnn_tiny.json",
                             "reduced": [], "why": "test"})
    cells = [("tiny_ru_sat", "cnn_tiny", "tiny_ru_saturate"),
             ("tiny_ru_flowcell", "step5", "tiny_ru_flowcell"),
             ("tiny_ru_paced", "step5", "tiny_ru_paced"),
             ("tiny_offline", "cnn_tiny", "tiny_offline_long")]
    for name, cfg, traf in cells:
        bench["workloads"].append({"name": name, "config": cfg,
                                   "traffic": traf, "chips": 1,
                                   "why": "test"})
    metric_cells = {"rt_channels": ["tiny_ru_sat", "tiny_ru_flowcell"],
                    "offline_samples_per_s": ["tiny_offline"]}
    for m in bench["end_to_end"]:
        if m["name"] in metric_cells:
            m["workloads"] += metric_cells[m["name"]]
    # a paced cell, as a later change would add one: a metric entry whose
    # reader is already in bench/metrics
    bench["end_to_end"].append(
        {"name": "decision_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["tiny_ru_paced"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
