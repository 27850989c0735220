"""The configurations of the benchmark reach their model family through
``bench.configs.model_of``, and for the conv stack every count, reference
token and reader's number is what the harness computed before the lookup
existed: ``bench/lib/cost.py`` and ``frame_classes`` + ``collapse``."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from bench.configs import model_of
from bench.configs import reference as ref
from bench.lib import cost
from benchtools import REPO, TINY_CNN, TINY_OFFLINE


def _cfg(name):
    return json.loads((REPO / f"bench/configs/{name}.json").read_text())


@pytest.mark.parametrize("name,params", [("cnn460k", 460_261),
                                         ("step5", 45)])
def test_existing_configs_reach_the_conv_reference(name, params):
    cfg = _cfg(name)
    assert "reference" not in cfg
    model = model_of(cfg)
    assert model is ref
    assert model.n_params(cfg) == cost.n_params(cfg) == params


def test_conv_family_accepts_what_the_file_test_accepted():
    assert ref.WEIGHT_KINDS == ("he_normal", "step_levels")
    assert ref.CONTROLS == ("bf16", "fp8", "int4")
    assert ref.FORWARD_MODULE == "jit__apply_jit"


@pytest.mark.parametrize("name,macs", [("cnn460k", 133_088),
                                       ("step5", 17.5)])
def test_conv_family_work_is_cost_exactly(name, macs):
    cfg = _cfg(name)
    model = model_of(cfg)
    assert model.forward_work(cfg, 512, 10_000) == cost.forward(
        cfg, 512, 10_000)
    assert model.macs_per_sample(cfg) == cost.macs_per_sample(cfg) == macs


def _tiny_offline_rows(cfg):
    from bench.lib import chunker
    traffic = json.loads((REPO / "bench/traffic/offline_long.json")
                         .read_text())
    traffic.update(TINY_OFFLINE)
    rows, _ = chunker.long_read_chunks(
        np.random.default_rng(traffic["pool_seed"]), traffic, cfg["signal"])
    return rows


@pytest.mark.parametrize("operands", ["f32", "fp8"])
def test_offline_reads_are_frame_classes_then_collapse(operands):
    """The tiny offline cell's rows, through the family's ``offline_reads``
    and through the composition the offline driver called before."""
    cfg = {**_cfg("cnn460k"), **TINY_CNN}
    rows = _tiny_offline_rows(cfg)
    params = ref.make_params(cfg, 2_300_000_017)
    got = model_of(cfg).offline_reads(params, cfg, rows, operands=operands)
    with jax.default_matmul_precision("highest"):
        classes = ref.frame_classes(params, cfg, rows, padding="same",
                                    operands=operands)
    want = [ref.collapse(c, len(c))[0] for c in classes]
    assert len(got) == len(want) == len(rows)
    assert sum(len(w) for w in want) > 0
    for g, w in zip(got, want):
        assert g.dtype == np.int32 and np.array_equal(g, w)


def _obs_with_forward_trace(cfg, per_call_s, calls=3):
    from bench.lib import trace as trace_lib
    ns = per_call_s * 1e9
    modules = {0: [(f"{ref.FORWARD_MODULE}({i})", i * 2 * ns,
                    i * 2 * ns + ns) for i in range(calls)]}
    tr = trace_lib.Trace(ops={}, modules=modules, host=[])
    return {"cfg": cfg, "batch": 512, "chunk": 10_000, "chips_used": 1,
            "device_kind": "TPU v5 lite", "trace": tr,
            "trace_summary": {"bounds_ns": (0.0, calls * 2 * ns)}}


def test_forward_roofline_reads_the_same_least_time():
    from bench.run import load_module
    cfg = _cfg("cnn460k")
    obs = _obs_with_forward_trace(cfg, 0.1265)
    reader = load_module(REPO / "bench/metrics/forward_roofline.py")
    least, bound = cost.least_time(cost.forward(cfg, 512, 10_000),
                                   cost.peaks("TPU v5 lite"))
    assert bound == "compute"
    assert reader.read(obs) == pytest.approx(least / 0.1265 * 100.0,
                                             rel=1e-12)


@pytest.mark.parametrize("name", ["cnn460k", "step5"])
def test_mfu_reads_the_same_flops(name):
    from bench.run import load_module
    cfg = _cfg(name)
    obs = {"cfg": cfg, "samples": 971_000_000, "window_s": 30.0,
           "chips_used": 1, "device_kind": "TPU v5 lite"}
    reader = load_module(REPO / "bench/metrics/mfu.py")
    want = (2.0 * 971_000_000 * cost.macs_per_sample(cfg)
            / (30.0 * 197e12) * 100.0)
    assert reader.read(obs) == want
