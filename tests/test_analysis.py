"""HLO weighted-cost analyzer + roofline model unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import analyze_hlo, parse_computations
from repro.analysis import roofline
from repro.configs import ARCHS


def test_scan_weighted_equals_unrolled():
    w = jax.random.normal(jax.random.key(0), (8, 128, 128), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (4, 128), jnp.float32)

    def scanned(w, x):
        def body(h, wi):
            return h @ wi, None
        h, _ = jax.lax.scan(body, x, w)
        return h.sum()

    def unrolled(w, x):
        h = x
        for i in range(8):
            h = h @ w[i]
        return h.sum()

    costs = {}
    for name, fn in (("scan", scanned), ("unroll", unrolled)):
        c = jax.jit(fn).lower(w, x).compile()
        costs[name] = analyze_hlo(c.as_text(), 1)
    want = 8 * 2 * 4 * 128 * 128
    assert costs["scan"].flops == want
    assert costs["unroll"].flops == want
    # built-in cost_analysis undercounts the scan (the bug we fix)
    builtin = jax.jit(scanned).lower(w, x).compile().cost_analysis()["flops"]
    assert builtin < want / 4


def test_nested_scan_multipliers():
    w = jax.random.normal(jax.random.key(0), (3, 4, 64, 64), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (8, 64), jnp.float32)

    def fn(w, x):
        def outer(h, wo):
            def inner(h2, wi):
                return h2 @ wi, None
            h, _ = jax.lax.scan(inner, h, wo)
            return h, None
        h, _ = jax.lax.scan(outer, x, w)
        return h.sum()

    c = jax.jit(fn).lower(w, x).compile()
    wc = analyze_hlo(c.as_text(), 1)
    assert wc.flops == 12 * 2 * 8 * 64 * 64


def test_collective_parsing_sharded_matmul():
    if jax.device_count() < 2:
        pytest.skip("needs >1 device (run in dryrun env)")


def test_parse_computations_structure():
    x = jnp.ones((16, 16))
    c = jax.jit(lambda a: (a @ a).sum()).lower(x).compile()
    comps, entry = parse_computations(c.as_text())
    assert entry is not None and entry in comps
    kinds = {op.kind for comp in comps.values() for op in comp.ops}
    assert "dot" in kinds


class TestRooflineModel:
    def test_model_params_close_to_nameplate(self):
        expect = {
            "qwen3-4b": 4.0e9, "nemotron-4-15b": 15.6e9,
            "starcoder2-3b": 3.2e9, "minicpm-2b": 2.7e9,
            "internvl2-76b": 70e9, "llama4-maverick-400b-a17b": 400e9,
            "grok-1-314b": 314e9, "mamba2-780m": 0.78e9,
            "whisper-medium": 0.8e9, "jamba-v0.1-52b": 52e9,
        }
        for arch, want in expect.items():
            cfg = ARCHS[arch].config()
            got = roofline.model_params(cfg)
            assert 0.75 * want < got < 1.3 * want, (arch, got, want)

    def test_active_params_moe(self):
        cfg = ARCHS["llama4-maverick-400b-a17b"].config()
        total = roofline.model_params(cfg)
        active = roofline.model_params(cfg, active=True)
        assert active < total / 10        # a17b vs 400b
        assert 8e9 < active < 25e9

    def test_model_flops_scaling(self):
        cfg = ARCHS["qwen3-4b"].config()
        f_train = roofline.model_flops(cfg, "train", 4096, 256)
        f_prefill = roofline.model_flops(cfg, "prefill", 4096, 256)
        assert f_train == pytest.approx(3 * f_prefill)
        f_decode = roofline.model_flops(cfg, "decode", 4096, 256)
        assert f_decode == pytest.approx(f_prefill / 4096)

    def test_analytic_memory_decode_wall(self):
        # decode must be memory-dominated by params + cache
        cfg = ARCHS["qwen3-4b"].config()
        b = roofline.analytic_memory_bytes(cfg, "decode", 32768, 128, 256)
        params_local = roofline.model_params(cfg) / 16 * 2
        assert b > params_local  # at least one param sweep

    def test_kv_cache_bytes(self):
        cfg = ARCHS["qwen3-4b"].config()
        got = roofline.kv_cache_bytes(cfg, 128, 32768)
        want = 128 * 32768 * 2 * 36 * cfg.kv_dim * 2
        assert got == want
