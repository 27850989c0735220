"""The program surfaces the harness reaches beyond ``build``, ``step()``,
``records`` and the telemetry: they exist and keep their shape, and the
harness stops with a clear message where one is gone."""
from __future__ import annotations

import types

import numpy as np
import pytest

import benchtools  # noqa: F401  (puts the checkout on the path)
from bench.drivers import readuntil as ru
from bench.lib import common


@pytest.fixture
def engine():
    from repro.engine import build
    rng = np.random.default_rng(0)
    genome = rng.integers(1, 5, size=2000).astype(np.int32)
    return build("adaptive_sampling", reference=genome, targets=[(0, 500)],
                 channels=8, chunk=64, pipeline_depth=2, fused=True,
                 mesh=None, seed=0)


def test_engine_and_runtime_have_what_the_harness_reads(engine):
    common.require(engine, "engine", "runtime", "flowcell", "records",
                   "telemetry", "flush")
    common.require(engine.runtime, "runtime", "_step", "_source", "warmup",
                   "mesh", "channels")
    common.require(engine.telemetry, "telemetry", "stage", "stage_s",
                   "steps", "fabric_counters")
    assert engine.runtime._source is None       # queue-fed until swapped


def test_recorder_keeps_one_base_count_per_lane(engine):
    rec = ru.StepRecorder(engine.runtime)
    engine.submit(np.random.default_rng(1).normal(size=300)
                  .astype(np.float32))
    for _ in range(3):
        engine.step()
    engine.flush()
    assert rec.dispatched() >= 1
    assert all(tuple(x.shape) == (8,) for x in rec.lens)


def test_missing_surface_stops_the_run():
    with pytest.raises(RuntimeError, match="_source"):
        common.require(types.SimpleNamespace(warmup=1), "runtime",
                       "warmup", "_source")


def test_recorder_refuses_a_step_of_another_shape():
    runtime = types.SimpleNamespace(channels=8,
                                    _step=lambda *a: (np.zeros(8),) * 2)
    rec = ru.StepRecorder(runtime)
    with pytest.raises(RuntimeError, match="jitted tick"):
        runtime._step()
