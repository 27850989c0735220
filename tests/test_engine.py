"""Unified engine substrate: SlotScheduler, Telemetry, registry, and the
deprecation shims (old API == new API, bit for bit, on fixed seeds).

Shim-warning tests here rely on the conftest ``_fresh_warning_registries``
autouse fixture: DeprecationWarnings dedupe once-per-location, so without
it an earlier test's shim call could swallow the one ``pytest.warns``
expects (an order-dependent failure in the full run)."""
import warnings

import jax
import numpy as np
import pytest

import repro.engine as engine_api
from repro.engine import SlotScheduler, Telemetry, weighted_percentile


# ----------------------------------------------------------- scheduler ----
class TestSlotScheduler:
    def test_admission_fills_lowest_slots_first(self):
        sched = SlotScheduler(4)
        sched.submit_all(["a", "b"])
        assert sched.admit() == [(0, "a"), (1, "b")]
        assert sched.busy == [0, 1]
        assert sched.pending == 0

    def test_release_recycles_slot(self):
        sched = SlotScheduler(2)
        sched.submit_all([1, 2, 3])
        sched.admit()
        assert sched.pending == 1
        assert sched.release(0) == 1
        assert sched.admit() == [(0, 3)]
        assert sched.drained is False
        sched.release(0), sched.release(1)
        assert sched.drained

    def test_depth_bounds_occupancy(self):
        sched = SlotScheduler(4, depth=2)
        sched.submit_all(range(4))
        assert len(sched.admit()) == 2
        assert sched.n_busy == 2
        sched.release(sched.oldest())
        assert len(sched.admit()) == 1

    def test_oldest_is_fifo(self):
        sched = SlotScheduler(3)
        sched.submit_all("xyz")
        sched.admit()
        assert sched.oldest() == 0
        sched.release(0)
        assert sched.oldest() == 1
        sched.submit("w")
        sched.admit()             # refills slot 0, now youngest
        assert sched.oldest() == 1

    def test_wrap_converts_payload(self):
        sched = SlotScheduler(2)
        sched.submit(5)
        out = sched.admit(wrap=lambda s, item: (s, item * 2))
        assert out == [(0, (0, 10))]
        assert sched.active[0] == (0, 10)

    def test_errors(self):
        with pytest.raises(ValueError):
            SlotScheduler(0)
        with pytest.raises(ValueError):
            SlotScheduler(2, depth=3)
        sched = SlotScheduler(2)
        with pytest.raises(ValueError):
            sched.release(0)


# ----------------------------------------------------------- telemetry ----
class TestTelemetry:
    def test_weighted_percentile_matches_repeat(self):
        rng = np.random.default_rng(0)
        vals = rng.normal(10, 3, 50)
        weights = rng.integers(1, 6, 50)
        expanded = np.repeat(vals, weights)
        for q in (50, 90, 99):
            got = weighted_percentile(vals, weights, q)
            want = np.percentile(expanded, q, method="inverted_cdf")
            assert abs(got - float(want)) < 1e-9

    def test_empty_latencies(self):
        tel = Telemetry()
        assert tel.latency_percentile(50) == 0.0
        assert tel.summary()["p99_ms"] == 0.0

    def test_counters_and_stages(self):
        tel = Telemetry(workload="x")
        tel.count("accepted")
        tel.count("accepted", 2)
        with tel.stage("map"):
            pass
        with tel.stage("map"):
            pass
        tel.observe_latency(5.0, weight=3)
        tel.samples, tel.samples_saved, tel.wall_s = 30, 70, 2.0
        s = tel.summary()
        assert s["accepted"] == 3
        assert s["stage_map_s"] >= 0.0
        assert s["p50_ms"] == 5.0
        assert s["signal_saved_frac"] == 0.7
        assert s["samples_per_s"] == pytest.approx(15.0)


# ------------------------------------------------------------ registry ----
class TestRegistry:
    def test_workload_listing(self):
        assert set(engine_api.workloads()) >= {
            "lm_decode", "basecall", "adaptive_sampling", "pathogen_pipeline"}

    def test_unknown_workload_and_preset(self):
        with pytest.raises(KeyError):
            engine_api.build("nope")
        with pytest.raises(KeyError):
            engine_api.build("basecall", preset="nope")

    def test_presets_and_overrides(self):
        assert engine_api.presets("basecall")["smoke"]["batch"] == 4
        eng = engine_api.build("basecall", preset="smoke", batch=2)
        assert eng.batch == 2 and eng.chunk == 512
        assert eng.workload == "basecall"
        assert isinstance(eng, engine_api.Engine)


# ------------------------------------------------- shims & equivalence ----
def _bc_setup(kernels=(3, 3, 1), channels=(16, 16, 5), strides=(1, 2, 1)):
    from repro.core import basecaller as bc
    cfg = bc.BasecallerConfig(kernels=kernels, channels=channels,
                              strides=strides)
    return cfg, bc.init(jax.random.key(0), cfg)


class TestDeprecationShims:
    def test_basecall_server_warns_and_matches(self):
        from repro.serving.engine import BasecallServer
        cfg, params = _bc_setup()
        rng = np.random.default_rng(0)
        chunks = rng.normal(size=(10, 512)).astype(np.float32)
        with pytest.warns(DeprecationWarning):
            srv = BasecallServer(params, cfg, batch=4, chunk=512)
        old = srv.serve(chunks)
        eng = engine_api.build("basecall", params=params, cfg=cfg,
                               batch=4, chunk=512)
        new = eng.serve(chunks)
        assert len(old) == len(new) == 10
        for a, b in zip(old, new):
            np.testing.assert_array_equal(a, b)
        assert srv.stats.samples == eng.telemetry.samples
        assert srv.stats.summary().keys() == {
            "p50_ms", "p99_ms", "bases_per_s", "samples_per_s"}

    def test_streaming_pipeline_warns_and_matches(self):
        from repro.core.pipeline import StreamingBasecallPipeline
        cfg, params = _bc_setup()
        rng = np.random.default_rng(7)
        chunks = [rng.normal(size=(4, 512)).astype(np.float32)
                  for _ in range(3)]
        with pytest.warns(DeprecationWarning):
            pipe = StreamingBasecallPipeline(params, cfg)
        old = list(pipe.run(iter(chunks)))
        assert pipe.stats.chunks == 3
        assert pipe.stats.samples_in == 3 * 4 * 512
        eng = engine_api.build("pathogen_pipeline", params=params, cfg=cfg)
        for chunk in chunks:
            eng.submit(chunk)
        eng.drain()
        assert len(old) == len(eng.outputs) == 3
        for (ot, ol), (nt, nl) in zip(old, eng.outputs):
            np.testing.assert_array_equal(ot, nt)
            np.testing.assert_array_equal(ol, nl)

    def test_lm_server_warns_and_matches(self, lm_smoke):
        from repro.engine.lm import Request
        from repro.serving.engine import LMServer
        model, params, cfg = lm_smoke

        def requests():
            rng = np.random.default_rng(0)
            return [Request(uid=uid,
                            prompt=rng.integers(1, cfg.vocab_size, 3),
                            max_new_tokens=4) for uid in range(4)]

        with pytest.warns(DeprecationWarning):
            srv = LMServer(model, params, cfg, slots=2, max_len=32)
        for r in requests():
            srv.submit(r)
        old_steps = srv.run_until_drained()
        eng = engine_api.build("lm_decode", model=model, params=params,
                               cfg=cfg, slots=2, max_len=32)
        for r in requests():
            eng.submit(r)
        report = eng.drain()
        # The shim delegates 1:1, so the scheduling/bookkeeping must match
        # exactly: steps, finished uids, tokens emitted per request (with
        # eos=-1 these are all value-independent).  Exact token VALUES are
        # deliberately not compared: two separate decode runs of the
        # random-init bf16 smoke model can legitimately diverge on CPU —
        # overlapping async dispatches shift multithreaded reduction
        # partitioning, and near-tie logits then flip argmax — so token
        # equality would test XLA run-to-run determinism, not the shim.
        assert old_steps == report["steps"]
        old_tokens = {r.uid: len(r.tokens_out) for r in srv.finished}
        new_tokens = {r.uid: len(r.tokens_out) for r in eng.finished}
        assert old_tokens == new_tokens
        assert [r.uid for r in srv.finished] == \
            [r.uid for r in eng.finished]

    def test_adaptive_server_warns_and_matches(self):
        from repro.data import genome as G
        from repro.serving.engine import AdaptiveSamplingServer
        cfg, params = _bc_setup(kernels=(5, 3), channels=(16, 5),
                                strides=(1, 2))
        rng = np.random.default_rng(3)
        reference = G.random_genome(rng, 3_000)
        signals = [rng.normal(size=700).astype(np.float32) for _ in range(6)]

        with pytest.warns(DeprecationWarning):
            srv = AdaptiveSamplingServer(params, cfg, reference, [(0, 1_000)],
                                         channels=4, chunk=128)
        for i, sig in enumerate(signals):
            srv.submit(sig, read_id=i, on_target=bool(i % 2))
        old = srv.run_until_drained(max_ticks=500)

        eng = engine_api.build("adaptive_sampling", params=params, cfg=cfg,
                               reference=reference, targets=[(0, 1_000)],
                               channels=4, chunk=128)
        for i, sig in enumerate(signals):
            eng.submit(sig, read_id=i, on_target=bool(i % 2))
        new = eng.drain(max_steps=500)

        assert old["reads"] == new["reads"] == 6
        for a, b in zip(srv.records, eng.records):
            assert (a.read_id, a.decision, a.reason, a.bases_at_decision,
                    a.samples_sequenced, a.mapped_pos) == \
                   (b.read_id, b.decision, b.reason, b.bases_at_decision,
                    b.samples_sequenced, b.mapped_pos)

    def test_new_api_emits_no_deprecation(self):
        cfg, params = _bc_setup()
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            engine_api.build("basecall", params=params, cfg=cfg,
                             batch=4, chunk=512)


# ------------------------------------------------- basecall readback ----
def _ragged_rows(rng, noise_lens, chunk=512):
    """Rows of noise over their first ``n`` samples and zeros after, so
    that they decode to different token counts."""
    rows = np.zeros((len(noise_lens), chunk), np.float32)
    for i, n in enumerate(noise_lens):
        rows[i, :n] = rng.normal(size=n)
    return rows


def _direct_decode(eng, rows):
    """Tokens and lengths of ``ctc.greedy_decode`` on the engine's logits."""
    import jax.numpy as jnp
    from repro.core import basecaller as bc, ctc
    logits = bc.apply(eng.params, jnp.asarray(rows), cfg=eng.cfg,
                      fabric=eng.fabric)
    tokens, lens = ctc.greedy_decode(logits)
    return np.asarray(tokens), np.asarray(lens)


class TestBasecallReadback:
    def test_reads_match_direct_decode(self):
        cfg, params = _bc_setup()
        eng = engine_api.build("basecall", params=params, cfg=cfg,
                               batch=4, chunk=512)
        rows = _ragged_rows(np.random.default_rng(0), [40, 120, 260, 512])
        tokens, lens = _direct_decode(eng, rows)
        assert len(set(lens.tolist())) == len(rows)
        eng.submit(rows)
        assert eng.step()
        assert len(eng.reads) == len(rows)
        for j, r in enumerate(eng.reads):
            assert isinstance(r, np.ndarray) and r.dtype == np.int32
            np.testing.assert_array_equal(r, tokens[j, :lens[j]])
            # a kept read owns its memory rather than viewing the batch
            assert r.base is None or r.flags.owndata
        assert eng.telemetry.bases == int(lens.sum())
        assert eng.telemetry.completed == len(rows)

    def test_new_token_counts_compile_nothing(self):
        cfg, params = _bc_setup()
        eng = engine_api.build("basecall", params=params, cfg=cfg,
                               batch=4, chunk=512)
        rng = np.random.default_rng(1)
        warm = _ragged_rows(rng, [40, 120, 260, 512])
        fresh = _ragged_rows(rng, [80, 180, 340, 450])
        tokens, warm_lens = _direct_decode(eng, warm)
        _, fresh_lens = _direct_decode(eng, fresh)
        assert not set(fresh_lens.tolist()) & set(warm_lens.tolist())
        eng.submit(warm)
        assert eng.step()
        compiles = []

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(secs)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            eng.submit(fresh)
            assert eng.step()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
        assert compiles == []
        assert [len(r) for r in eng.reads] == (
            warm_lens.tolist() + fresh_lens.tolist())
        assert eng.telemetry.counters["readback.bytes"] == 2 * (
            tokens.nbytes + warm_lens.nbytes)


@pytest.fixture(scope="module")
def lm_smoke():
    from repro.configs import ARCHS
    from repro.models.registry import get_model
    cfg = ARCHS["qwen3-4b"].smoke_config()
    model = get_model(cfg)
    params, _ = model.init(jax.random.key(0), cfg)
    return model, params, cfg


# --------------------------------------------------------- trim_primer ----
def _trim_primer_reference(tokens, lens, primer_len):
    """The original per-read Python loop (kept as the behavioural oracle)."""
    out = np.zeros_like(tokens)
    new_lens = np.maximum(lens - primer_len, 0)
    for i in range(tokens.shape[0]):
        out[i, : new_lens[i]] = tokens[i, primer_len: lens[i]]
    return out, new_lens


class TestTrimPrimerVectorized:
    @pytest.mark.parametrize("primer_len", [0, 1, 3, 7, 64])
    def test_matches_reference_loop(self, primer_len):
        rng = np.random.default_rng(42)
        tokens = rng.integers(1, 5, size=(32, 48)).astype(np.int32)
        lens = rng.integers(0, 49, size=32)
        for i in range(32):
            tokens[i, lens[i]:] = 0
        from repro.core.pipeline import trim_primer
        got, got_lens = trim_primer(tokens, lens, primer_len)
        want, want_lens = _trim_primer_reference(tokens, lens, primer_len)
        np.testing.assert_array_equal(got_lens, want_lens)
        np.testing.assert_array_equal(got, want)
        assert got.dtype == tokens.dtype
