"""``chip_smoke.py`` on the CPU: its comparison helpers, and every phase at
a tiny size with the kernels interpreted, so the script cannot rot between
chip runs.  The chip-only assertions (compiled kernels, no TPU -> exit)
are steered here in the test, not through options of the script."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

INTERPRET = "pallas_interpret"


def _levenshtein(a, b):
    d = list(range(len(b) + 1))
    for i, x in enumerate(a, 1):
        prev, d[0] = d[:], i
        for j, y in enumerate(b, 1):
            d[j] = min(prev[j] + 1, d[j - 1] + 1, prev[j - 1] + (x != y))
    return d[-1]


def test_edit_distances_match_plain_dp():
    rng = np.random.default_rng(0)
    a = rng.integers(1, 5, (64, 12))
    b = rng.integers(1, 5, (64, 10))
    la = rng.integers(0, 13, 64)
    lb = rng.integers(0, 11, 64)
    want = [_levenshtein(a[i, :la[i]], b[i, :lb[i]]) for i in range(64)]
    np.testing.assert_array_equal(cs.edit_distances(a, la, b, lb), want)


def test_token_agreement_ignores_padding_past_length():
    ref = np.asarray([[1, 2, 3, 0], [4, 4, 1, 0]])
    lens = np.asarray([3, 3])
    got = ref.copy()
    got[0, 3] = 2                       # past the length: not a difference
    assert cs.token_agreement(got, lens, ref, lens) == (1.0, 0)
    got[1, 1] = 3                       # one substitution in 6 tokens
    agree, n_diff = cs.token_agreement(got, lens, ref, lens)
    assert n_diff == 1 and agree == pytest.approx(1 - 1 / 6)


@pytest.fixture()
def checked(monkeypatch):
    """Record each phase's counter deltas instead of demanding compiled
    kernels (interpret mode is the CPU's stand-in for them)."""
    seen = {}
    monkeypatch.setattr(cs, "check_counters",
                        lambda phase, delta, fused: seen.update({phase: delta}))
    return seen


def test_phases_run_at_tiny_size(checked):
    cs.phase_flowcell(0, channels=16, fabric=INTERPRET,
                      flowcell={"encoder": "step", "n_reads": 32,
                                "read_len": (96, 160)})
    out = cs.phase_paper(0, channels=16, ticks=3, fabric=INTERPRET)
    # interpret mode on the CPU computes what the reference computes
    assert out["logit_err"] == 0 and out["agreement"] == 1.0
    out = cs.phase_basecall(0, rows=8, batch=4, chunk=512, fabric=INTERPRET)
    assert out["logit_err"] == 0 and out["agreement"] == 1.0
    for phase in ("a", "b"):
        d = checked[phase]
        assert d.get(f"fabric.dispatch.fused_stream.{INTERPRET}", 0) > 0
        assert d.get(f"fabric.dispatch.banded_align.{INTERPRET}", 0) > 0
        assert not any(k.startswith("fabric.fallback.fused_stream.")
                       for k in d)
    assert checked["c"].get(f"fabric.dispatch.conv1d.{INTERPRET}", 0) > 0


_MESH_SCRIPT = r"""
import sys
sys.path.insert(0, {repo!r})
import chip_smoke as cs
cs.phase_mesh(0, 4, channels=32, ticks=3, fabric="pallas_interpret")
print("MESH_OK")
"""


def test_mesh_phase_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c",
                           _MESH_SCRIPT.format(repo=REPO)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH_OK" in proc.stdout


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=REPO)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
