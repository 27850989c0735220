"""``correct`` fails where it must: the control (the reference at a lower
precision than the configuration's) and runs whose timed path is broken
underneath come out not correct, on tiny cells on the CPU."""
from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchtools import REPO
from test_bench_drivers import _run_cell


def _cfg(name):
    return json.loads((REPO / f"bench/configs/{name}.json").read_text())


def _pool_mismatch(cfg, operands, n=32, seed=5):
    """Token mismatch of the reference at ``operands`` against float32, on
    ``n`` pool molecules of the configuration's own signal."""
    from bench.configs import reference as ref
    from bench.lib import common, signals

    rng = np.random.default_rng(seed)
    genome = signals.random_genome(rng, 4000)
    pool = signals.molecule_pool(rng, genome, np.zeros(4000, bool), n,
                                 (150, 400), cfg["signal"])
    width = int(pool.lengths().max())
    x = np.zeros((n, width), np.float32)
    for m in range(n):
        x[m, :pool.lengths()[m]] = pool.molecule(m)
    params = ref.make_params(cfg, seed)
    want = ref.frame_classes(params, cfg, x, padding="stream")
    got = ref.frame_classes(params, cfg, x, padding="stream",
                            operands=operands)
    stride = int(np.prod(cfg["strides"]))
    valid = pool.lengths() // stride
    a = [ref.collapse(got[m], valid[m])[0] for m in range(n)]
    b = [ref.collapse(want[m], valid[m])[0] for m in range(n)]
    return common.token_mismatch(a, b)[0]


def test_control_fails_the_paper_cnn():
    cfg = _cfg("cnn460k")
    rate = _pool_mismatch(cfg, "fp8")
    assert rate > cfg["limits"]["readuntil"]["token_mismatch"]
    assert rate > cfg["limits"]["offline"]["token_mismatch"]


def test_step_decoder_is_exact_down_to_fp8_and_int4_breaks_it():
    cfg = _cfg("step5")
    assert _pool_mismatch(cfg, "fp8") == 0.0
    assert _pool_mismatch(cfg, "int4") > cfg["limits"]["readuntil"][
        "token_mismatch"]


def _broken_step(monkeypatch, fault):
    from repro.realtime import runtime as rt
    orig = rt.build_step_fn

    def build(cfg, fabric, mesh=None, fused=False):
        step = orig(cfg, fabric, mesh, fused)

        def broken(params, lane, *args):
            tokens, lens, new = step(params, lane, *args)
            if fault == "token":
                tokens = jnp.where(tokens > 0, tokens % 4 + 1, tokens)
                return tokens, lens, new
            if fault == "half":                # half of the lanes left out
                half = lens.shape[0] // 2
                return (tokens.at[half:].set(0), lens.at[half:].set(0),
                        new)
            return tokens, lens, lane          # state left unchanged
        return broken

    monkeypatch.setattr(rt, "build_step_fn", build)


@pytest.mark.parametrize("cell", ["tiny_ru_sat", "tiny_ru_flowcell",
                                  "tiny_ru_paced"])
@pytest.mark.parametrize("fault", ["token", "state", "half"])
def test_broken_read_until_step_is_not_correct(tiny_root, capsys,
                                                monkeypatch, cell, fault):
    _broken_step(monkeypatch, fault)
    res, _, _ = _run_cell(tiny_root, cell, capsys)
    assert res["correct"] is False


@pytest.mark.parametrize("fault", ["half_batch", "token"])
def test_broken_offline_engine_is_not_correct(tiny_root, capsys,
                                              monkeypatch, fault):
    from repro.core import basecaller as bc
    from repro.core import ctc
    if fault == "half_batch":
        orig = bc.apply

        def half(params, x, cfg, **kw):
            out = orig(params, x, cfg, **kw)
            return out.at[x.shape[0] // 2:].set(0.0)   # rows never computed
        monkeypatch.setattr(bc, "apply", half)
    else:
        orig = ctc.greedy_decode

        def altered(logits, paddings=None):
            tokens, lens = orig(logits, paddings)
            return jnp.where(tokens > 0, tokens % 4 + 1, tokens), lens
        monkeypatch.setattr(ctc, "greedy_decode", altered)
    res, _, _ = _run_cell(tiny_root, "tiny_offline", capsys)
    assert res["correct"] is False


def test_control_through_the_harness_is_not_correct(tiny_root, capsys):
    """The step decoder's control (int4) in the program's place, through
    ``run.main``'s own comparison."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("tiny_bench_run_ctl",
                                                  tiny_root / "bench/run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    res = run.main(["--workload", "tiny_ru_flowcell", "--seed", "2300000019",
                    "--seconds", "1", "--trace", "0"], root=tiny_root,
                   require_tpu=False, compile_cache=False,
                   control=_cfg("step5")["control"])
    out = capsys.readouterr().out
    info = json.loads([ln for ln in out.splitlines()
                       if ln.startswith("info ")][-1][5:])
    assert res["correct"] is False
    assert res["checks"]["token_mismatch"]["value"] > 0
    assert info["program_checks"]["token_mismatch"]["value"] == 0


@pytest.mark.parametrize("genome_len", [3000, 20000])
def test_reference_mapper_seeds_as_the_program_does(genome_len):
    """Hits of real and zero-filled seeds (the tails of windows of 32 to 47
    bases) against the program's FM-index search, and the suffix array."""
    import jax.numpy as jnp

    from bench.lib import mapper_ref
    from repro.core import fm_index

    rng = np.random.default_rng(genome_len)
    genome = rng.integers(1, 5, size=genome_len).astype(np.int32)
    seq = np.concatenate([genome, [0]])
    assert np.array_equal(mapper_ref.suffix_array(genome),
                          fm_index.suffix_array(seq))
    align = json.loads((REPO / "bench/traffic/ru_flowcell.json")
                       .read_text())["align"]
    ref = mapper_ref.ReferenceMapper(genome, np.zeros(genome_len, bool),
                                     align, {})
    k = align["seed_len"]
    starts = rng.integers(0, genome_len - k, size=300)
    seeds = np.stack([genome[s:s + k] for s in starts])
    seeds[:100, :2] = rng.integers(1, 5, size=(100, 2))   # some mismatch
    for z in range(1, k + 1):                               # padding tails
        seeds[100 + 10 * z: 110 + 10 * z, k - z:] = 0
    index = fm_index.FMIndex.build(genome)
    _, want = fm_index.backward_search(index.device_arrays(),
                                       jnp.asarray(seeds),
                                       max_hits=align["max_hits"])
    assert np.array_equal(ref.hits(seeds), np.asarray(want))


def test_reference_mapper_decides_padded_windows_as_the_program_does():
    """Windows of 32 to 47 called bases, zero-filled at the tail: the
    reference's decisions against the program's mapper and policy."""
    from bench.lib import mapper_ref
    from repro.realtime import policy as policy_mod
    from repro.realtime.mapper import PrefixMapper, TargetPanel

    traffic = json.loads((REPO / "bench/traffic/ru_flowcell.json").read_text())
    rng = np.random.default_rng(7)
    n = 40000
    genome = rng.integers(1, 5, size=n).astype(np.int32)
    mask = np.zeros(n, bool)
    mask[:n // 4] = True
    windows = np.zeros((64, 48), np.int32)
    lens = rng.integers(32, 49, size=64)
    for i, ln in enumerate(lens):
        s = rng.integers(0, n - ln)
        w = genome[s:s + ln].copy()
        flip = rng.random(ln) < 0.05
        w[flip] = rng.integers(1, 5, size=int(flip.sum()))
        windows[i, :ln] = w
    prog = PrefixMapper(TargetPanel.build(genome, [(0, n // 4)]))
    res = prog.map_prefixes(windows)
    pol = policy_mod.PolicyConfig()
    dec, why = policy_mod.decide(res.mapped, res.on_target, res.mapq, lens,
                                 pol)
    ref = mapper_ref.ReferenceMapper(genome, mask, traffic["align"],
                                     traffic["policy"])
    got = ref.decide(windows, lens)
    assert [(d.value, r) for d, r in zip(dec, why)] == \
        [(g[0], g[1]) for g in got]
    assert any(g[0] != "wait" for g in got)
