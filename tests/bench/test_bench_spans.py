"""The readers of the program's own spans: the mapper's four phases, the
Read-Until device wait and the offline readback.  Each gives ms per busy
tick (or per batch) of its stage, and nothing where the program has no
such stage, as a checkout from before the spans has not."""
from __future__ import annotations

import json

import pytest

from benchtools import REPO

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())

PER_TICK = {"map_upload_ms": "map.upload", "map_search_ms": "map.search",
            "map_vote_ms": "map.vote", "map_extend_ms": "map.extend",
            "step_wait_ms": "basecall.wait"}
SPAN_METRICS = {
    "map_upload_ms.ru", "map_upload_ms.flowcell", "map_search_ms.ru",
    "map_search_ms.flowcell", "map_vote_ms.ru", "map_vote_ms.flowcell",
    "map_extend_ms.ru", "map_extend_ms.flowcell", "step_wait_ms.ru",
    "readback_ms.offline"}


def _reader(metric):
    from bench.run import load_module, reader_path
    return load_module(reader_path(REPO, metric))


@pytest.mark.parametrize("name,stage", sorted(PER_TICK.items()))
def test_per_tick_reader_reads_its_stage(name, stage):
    read = _reader(name).read
    obs = {"busy_ticks": 4,
           "stage_s": {"map": 2.0, "basecall": 0.5, stage: 0.2}}
    assert read(obs) == pytest.approx(0.2 / 4 * 1e3)
    obs["stage_s"].pop(stage)
    assert read(obs) is None
    assert read({"busy_ticks": 0, "stage_s": {stage: 0.2}}) is None


def test_readback_reader_reads_per_batch():
    read = _reader("readback_ms.offline").read
    obs = {"batches_run": 5, "step_s": 9.5,
           "stage_s": {"basecall": 0.1, "decode": 0.2, "readback": 9.0}}
    assert read(obs) == pytest.approx(9.0 / 5 * 1e3)
    assert read(obs) <= _reader("readout_ms.offline").read(obs)
    assert read({**obs, "batches_run": 0}) is None
    assert read({**obs, "stage_s": {"basecall": 0.1}}) is None


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_metric_is_declared_with_its_cells(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_span"
    cells = {"ru": "ru_cnn460k_sat", "flowcell": "ru_step5_flowcell",
             "offline": "offline_cnn460k_long"}
    assert entry["workloads"] == [cells[name.split(".")[1]]]
    assert callable(_reader(name).read)
