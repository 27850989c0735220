"""The trace reduction: busy/idle union, per-module device time and idle
gaps by host annotation, on a hand-made trace and on a small trace
recorded on a TPU v5e and kept under ``bench/fixtures``."""
from __future__ import annotations

import numpy as np
import pytest

from bench.lib import trace as T
from benchtools import REPO

FIXTURE = REPO / "bench" / "fixtures" / "ru_tiny.xplane.pb"


def _hand_made():
    ms = 1e6
    ops = {0: [("a", 1 * ms, 3 * ms), ("b", 2 * ms, 4 * ms),
               ("a", 6 * ms, 7 * ms), ("c", 9.5 * ms, 12 * ms)]}
    modules = {0: [("jit_step(7)", 1 * ms, 4 * ms),
                   ("jit_step(7)", 6 * ms, 7 * ms),
                   ("jit_step_other(3)", 9.5 * ms, 10 * ms)]}
    host = [(T.WINDOW, 0.0, 10 * ms), ("bench.step", 0.5 * ms, 5 * ms),
            ("stage.sense", 4.2 * ms, 4.8 * ms),
            ("bench.pace_wait", 7 * ms, 9.5 * ms)]
    return T.Trace(ops=ops, modules=modules, host=host)


def test_union_and_idle_on_a_hand_made_trace():
    tr = _hand_made()
    s = T.reduce(tr, [0])
    # busy: [1,4] + [6,7] + [9.5,10] (clipped to the window) = 4.5 ms
    assert s["window_s"] == pytest.approx(0.010)
    assert s["busy_s"] == pytest.approx(0.0045)
    idle = dict(s["idle_gaps"])
    # gaps [0,1], [4,6], [7,9.5]: bench.step covers [0.5,1] and [4,5]
    # less stage.sense's [4.2,4.8]; [0,0.5] and [5,6] are unannotated
    assert idle["bench.step"] == pytest.approx(0.0009)
    assert idle["stage.sense"] == pytest.approx(0.0006)
    assert idle["host.unannotated"] == pytest.approx(0.0015)
    assert idle["bench.pace_wait"] == pytest.approx(0.0025)
    assert sum(idle.values()) == pytest.approx(0.010 - 0.0045)
    ops = dict(s["device_ops"])
    assert ops["a"] == pytest.approx(0.003)   # [1,3] + [6,7]
    assert ops["b"] == pytest.approx(0.002)


def test_innermost_annotation_takes_the_gap():
    ms = 1e6
    tr = T.Trace(ops={0: [("x", 0.0, 1 * ms), ("x", 3 * ms, 4 * ms)]},
                 modules={}, host=[(T.WINDOW, 0.0, 4 * ms),
                                   ("bench.step", 0.5 * ms, 3.5 * ms),
                                   ("stage.map", 1.5 * ms, 2.5 * ms)])
    idle = dict(T.reduce(tr, [0])["idle_gaps"])
    assert idle == {"stage.map": pytest.approx(0.001),
                    "bench.step": pytest.approx(0.001)}


def test_module_time_matches_by_name_only():
    tr = _hand_made()
    total, calls = T.module_time(tr, "jit_step", 0.0, 1e8)
    assert calls == 2 and total == pytest.approx(0.004)


def test_window_is_required():
    tr = _hand_made()
    tr.host = [h for h in tr.host if h[0] != T.WINDOW]
    with pytest.raises(ValueError):
        T.reduce(tr, [0])


def _brute_busy(events, lo, hi, step=1000.0):
    """Busy time by sampling every microsecond: independent of the
    interval merge."""
    grid = np.arange(lo, hi, step)
    busy = np.zeros(len(grid), bool)
    for _, s, e in events:
        busy[(grid >= s) & (grid < e)] = True
    return busy.sum() * step


def test_recorded_chip_trace():
    tr = T.load(str(FIXTURE))
    assert tr.ops and tr.modules, "no TPU device plane in the fixture"
    dev = sorted(tr.ops)[0]
    s = T.reduce(tr, [dev])
    lo, hi = T.window(tr)
    assert 0 < s["busy_s"] < s["window_s"]
    brute = _brute_busy(tr.ops[dev], lo, hi) * 1e-9
    assert s["busy_s"] == pytest.approx(brute, rel=0.02, abs=2e-5)
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6, abs=1e-9)
    step_s, calls = T.module_time(tr, "jit_step", lo, hi, [dev])
    assert calls > 0 and 0 < step_s < s["busy_s"] + 1e-9
    names = {n for n, _, _ in tr.host}
    assert {"bench.window", "bench.step"} <= names
