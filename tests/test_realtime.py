"""Adaptive-sampling runtime: streaming equivalence, policy, end-to-end."""
import contextlib
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import basecaller as bc
from repro.core import ctc
from repro.data import genome as G
from repro.kernels import ops
from repro.realtime import (AdaptiveSamplingRuntime, Decision, PolicyConfig,
                            PrefixMapper, SimulatedRead, TargetPanel, decide)


# ------------------------------------------------------- streaming convs --
class TestStreamingConv:
    @pytest.mark.parametrize("stride", [1, 2])
    def test_conv1d_stream_matches_whole(self, stride):
        k = jax.random.key(0)
        ksize, cin, cout = 5, 8, 16
        x = jax.random.normal(k, (2, 48, cin))
        w = jax.random.normal(jax.random.fold_in(k, 1), (ksize, cin, cout))
        b = jax.random.normal(jax.random.fold_in(k, 2), (cout,))
        whole, _ = ops.conv1d_stream(x, w, b, None, stride=stride,
                                     activation="relu", fabric="reference")
        carry = None
        outs = []
        for lo, hi in ((0, 16), (16, 20), (20, 48)):
            y, carry = ops.conv1d_stream(x[:, lo:hi], w, b, carry,
                                         stride=stride, activation="relu",
                                         fabric="reference")
            outs.append(y)
        np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)),
                                   np.asarray(whole), atol=1e-6)

    def test_conv1d_stream_kernel_path(self):
        """Interpret-mode Pallas kernel agrees with the oracle, chunked."""
        k = jax.random.key(3)
        x = jax.random.normal(k, (1, 32, 8))
        w = jax.random.normal(jax.random.fold_in(k, 1), (3, 8, 128))
        ref_y, _ = ops.conv1d_stream(x, w, None, None, stride=2,
                                     fabric="reference")
        carry = None
        outs = []
        for lo, hi in ((0, 16), (16, 32)):
            y, carry = ops.conv1d_stream(x[:, lo:hi], w, None, carry,
                                         stride=2,
                                         fabric="pallas_interpret")
            outs.append(y)
        np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)),
                                   np.asarray(ref_y), atol=1e-5)

    def test_rejects_misaligned_chunk(self):
        x = jnp.zeros((1, 33, 4))
        w = jnp.zeros((5, 4, 8))
        with pytest.raises(ValueError):
            ops.conv1d_stream(x, w, None, None, stride=2)


class TestStatefulBasecaller:
    def test_chunked_matches_whole_read(self):
        """The acceptance property: chunked logits == whole-read logits."""
        cfg = bc.BasecallerConfig()
        params = bc.init(jax.random.key(0), cfg)
        sig = jax.random.normal(jax.random.key(1), (3, 256))
        whole = bc.apply(params, sig, cfg, padding="stream")
        assert whole.shape == (3, 256 // cfg.total_stride, 5)

        state = bc.init_stream_state(cfg, 3)
        outs = []
        for lo, hi in ((0, 64), (64, 68), (68, 168), (168, 256)):
            y, state = bc.apply_stream(params, state, sig[:, lo:hi], cfg)
            assert y.shape[1] == (hi - lo) // cfg.total_stride
            outs.append(y)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate(outs, axis=1)), np.asarray(whole),
            atol=1e-5)

    def test_lane_reset_equals_fresh_stream(self):
        cfg = bc.BasecallerConfig(kernels=(5, 3), channels=(16, 5),
                                  strides=(1, 2))
        params = bc.init(jax.random.key(0), cfg)
        sig = jax.random.normal(jax.random.key(1), (2, 64))
        # pollute lane 0 with unrelated signal, then reset it
        state = bc.init_stream_state(cfg, 2)
        _, state = bc.apply_stream(params, state,
                                   jax.random.normal(jax.random.key(2),
                                                     (2, 32)), cfg)
        state = [s.at[jnp.asarray([0])].set(0) for s in state]
        y, _ = bc.apply_stream(params, state, sig, cfg)
        fresh, _ = bc.apply_stream(params, bc.init_stream_state(cfg, 2), sig,
                                   cfg)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(fresh[0]),
                                   atol=1e-6)

    def test_state_spec_shapes(self):
        cfg = bc.BasecallerConfig()
        state = bc.init_stream_state(cfg, 7)
        assert len(state) == len(cfg.kernels)
        for s, (rows, cin) in zip(state, bc.stream_state_spec(cfg)):
            assert s.shape == (7, rows, cin)


class TestStreamingCTC:
    def test_stream_decode_matches_whole(self):
        logits = jax.random.normal(jax.random.key(0), (4, 60, 5))
        tok_w, len_w = ctc.greedy_decode(logits)
        prev = jnp.full((4,), ctc.BLANK, jnp.int32)
        got = [[] for _ in range(4)]
        for lo, hi in ((0, 13), (13, 14), (14, 40), (40, 60)):
            tk, ln, prev = ctc.greedy_decode_stream(logits[:, lo:hi], prev)
            for b in range(4):
                got[b].extend(np.asarray(tk[b][: int(ln[b])]).tolist())
        for b in range(4):
            want = np.asarray(tok_w[b][: int(len_w[b])]).tolist()
            assert got[b] == want

    def test_stream_decode_padded_frames_emit_nothing(self):
        # strongly non-blank logits everywhere, but the tail is padding
        logits = jnp.zeros((2, 10, 5)).at[:, :, 2].set(10.0)
        pads = jnp.zeros((2, 10)).at[:, 6:].set(1.0)
        prev = jnp.full((2,), ctc.BLANK, jnp.int32)
        tk, ln, new_prev = ctc.greedy_decode_stream(logits, prev, pads)
        # frames 0..5 collapse to one 'C'; padded frames add nothing
        assert ln.tolist() == [1, 1]
        assert tk[:, 0].tolist() == [2, 2]
        # padded tail resets the carry to BLANK for the next read
        assert new_prev.tolist() == [ctc.BLANK, ctc.BLANK]


# ----------------------------------------------------------- policy/maps --
class TestPolicy:
    def test_decision_rules(self):
        cfg = PolicyConfig(min_mapq=4.0, max_prefix_bases=100)
        mapped = np.array([True, True, True, False, False])
        on_target = np.array([True, False, False, False, False])
        mapq = np.array([0.0, 10.0, 1.0, 0.0, 0.0])
        plen = np.array([50, 50, 50, 50, 120])
        decisions, reasons = decide(mapped, on_target, mapq, plen, cfg)
        assert decisions[0] is Decision.ACCEPT      # on-target
        assert decisions[1] is Decision.EJECT       # confident off-target
        assert decisions[2] is Decision.WAIT        # low-confidence eject
        assert decisions[3] is Decision.WAIT        # unmapped, patience left
        assert decisions[4] is cfg.timeout_decision  # out of patience
        assert reasons[4] == "timeout" and reasons[1] == "mapped"

    def test_panel_mask(self):
        panel = TargetPanel.build(np.ones(100, np.int32),
                                  [(10, 20), (90, 200)])
        assert panel.target_mask[10] and panel.target_mask[19]
        assert not panel.target_mask[20] and panel.target_mask[99]
        assert panel.intervals == ((10, 20), (90, 100))
        assert 0.19 < panel.target_frac < 0.21


class TestPrefixMapper:
    def test_exact_prefixes_classified(self, rng):
        ref = G.random_genome(rng, 6_000)
        panel = TargetPanel.build(ref, [(0, 3_000)])
        mapper = PrefixMapper(panel)
        L = 48
        starts = [100, 1_500, 3_500, 5_000]
        prefixes = np.stack([ref[s: s + L] for s in starts])
        res = mapper.map_prefixes(prefixes)
        assert res.mapped.all()
        np.testing.assert_array_equal(res.on_target,
                                      [True, True, False, False])
        for s, p in zip(starts, res.positions):
            assert abs(int(p) - s) <= 16

    def test_phases_cover_the_call_in_order(self, rng):
        """The four phases open in order, one each per call, and the result
        says which rows voted and how many index bytes went up."""
        ref = G.random_genome(rng, 6_000)
        mapper = PrefixMapper(TargetPanel.build(ref, [(0, 3_000)]))
        prefixes = np.stack([ref[s: s + 48] for s in (100, 2_000)])
        opened = []

        def stage(name):
            opened.append(name)
            return contextlib.nullcontext()

        res = mapper.map_prefixes(prefixes, stage=stage)
        assert opened == ["map.upload", "map.search", "map.vote",
                          "map.extend"]
        assert res.voted.all() and res.mapped.all()
        assert res.upload_bytes == sum(
            a.nbytes for a in mapper.index.device_arrays().values())


# ------------------------------------------------------------- runtime ----
class TestRuntime:
    def _runtime(self, rng, timeout_decision):
        cfg = bc.BasecallerConfig(kernels=(5, 3), channels=(16, 5),
                                  strides=(1, 2))
        params = bc.init(jax.random.key(0), cfg)
        ref = G.random_genome(rng, 4_000)
        panel = TargetPanel.build(ref, [(0, 1_000)])
        policy = PolicyConfig(min_prefix_bases=16, map_prefix_bases=24,
                              max_prefix_bases=48,
                              timeout_decision=timeout_decision,
                              eject_latency_samples=32)
        return AdaptiveSamplingRuntime(
            params, cfg, PrefixMapper(panel), policy, channels=4,
            chunk_samples=128), rng

    def test_every_read_resolves(self, rng):
        runtime, rng = self._runtime(rng, Decision.ACCEPT)
        reads = [SimulatedRead(
            signal=rng.normal(size=600).astype(np.float32), read_id=i,
            on_target=bool(i % 2)) for i in range(10)]
        runtime.submit_all(reads)
        report = runtime.run(max_ticks=500)
        assert report["reads"] == 10
        assert len(runtime.records) == 10
        for rec in runtime.records:
            assert 0 < rec.samples_sequenced <= rec.total_samples
            assert rec.samples_saved == rec.total_samples - rec.samples_sequenced
            assert rec.reason in ("mapped", "timeout", "exhausted")
        assert 0.0 <= report["signal_saved_frac"] <= 1.0
        assert report["decision_p99_ms"] >= report["decision_p50_ms"]

    def test_eject_saves_signal(self, rng):
        """With an eject-on-timeout policy every undecidable read saves
        signal — exercises the eject bookkeeping deterministically."""
        runtime, rng = self._runtime(rng, Decision.EJECT)
        runtime.submit_all([
            SimulatedRead(signal=rng.normal(size=900).astype(np.float32),
                          read_id=i) for i in range(6)])
        report = runtime.run(max_ticks=500)
        assert report["ejected"] + report["timeouts"] + report["accepted"] \
            + report["exhausted"] == 6
        assert report["signal_saved_frac"] > 0.0
        assert runtime.telemetry.samples_saved + runtime.telemetry.samples \
            == 6 * 900

    def test_rejects_misaligned_chunk_size(self, rng):
        cfg = bc.BasecallerConfig()
        params = bc.init(jax.random.key(0), cfg)
        panel = TargetPanel.build(G.random_genome(rng, 1_000), [(0, 100)])
        with pytest.raises(ValueError):
            AdaptiveSamplingRuntime(params, cfg, PrefixMapper(panel),
                                    PolicyConfig(), channels=2,
                                    chunk_samples=130)

    def test_pipelined_report_counts_match_submitted(self, rng):
        """Double-buffered runtime: the final in-flight tick's observations
        are flushed by run(), so report counts equal submitted reads and
        the decision-latency aliases cover every decided read."""
        runtime, rng = self._runtime(rng, Decision.EJECT)
        runtime.pipeline_depth = 2
        n = 7
        runtime.submit_all([
            SimulatedRead(signal=rng.normal(size=700).astype(np.float32),
                          read_id=i) for i in range(n)])
        report = runtime.run(max_ticks=500)
        assert report["reads"] == n
        assert len(runtime.records) == n
        assert (report["accepted"] + report["ejected"] + report["timeouts"]
                + report["exhausted"]) == n
        decided = (report["accepted"] + report["ejected"]
                   + report["timeouts"])
        assert len(runtime.telemetry.latencies_ms) == decided


# ------------------------------------------------ program's own spans ----
class TestProgramSpans:
    """The runtime names the mapper's phases and the device wait as child
    stages, counts each map call's rows and index bytes, and opens the
    stages on the profiler's clock by itself."""

    MAP_PHASES = ("map.upload", "map.search", "map.vote", "map.extend")

    def _runtime(self):
        rng = np.random.default_rng(7)
        cfg = bc.BasecallerConfig(kernels=(5, 3), channels=(16, 5),
                                  strides=(1, 2))
        params = bc.init(jax.random.key(0), cfg)
        panel = TargetPanel.build(G.random_genome(rng, 4_000), [(0, 1_000)])
        policy = PolicyConfig(min_prefix_bases=8, map_prefix_bases=24,
                              max_prefix_bases=48,
                              timeout_decision=Decision.ACCEPT,
                              eject_latency_samples=32)
        runtime = AdaptiveSamplingRuntime(
            params, cfg, PrefixMapper(panel), policy, channels=4,
            chunk_samples=128)
        runtime.submit_all([
            SimulatedRead(signal=rng.normal(size=600).astype(np.float32),
                          read_id=i) for i in range(6)])
        return runtime

    def test_children_fit_inside_their_parents(self):
        runtime = self._runtime()
        runtime.run(max_ticks=200)
        st = runtime.telemetry.stage_s
        assert st["map"] > 0
        for name in self.MAP_PHASES:
            assert 0 < st[name] <= st["map"], name
        assert sum(st[n] for n in self.MAP_PHASES) <= st["map"]
        assert 0 < st["basecall.wait"] <= st["basecall"]

    def test_map_counters_add_up(self):
        runtime = self._runtime()
        runtime.warmup()        # the warm-up map call is not counted
        calls = []
        inner = runtime.mapper.map_prefixes

        def counted(prefixes, **kw):
            calls.append(len(prefixes))
            return inner(prefixes, **kw)
        runtime.mapper.map_prefixes = counted
        report = runtime.run(max_ticks=200)
        c = runtime.telemetry.counters
        rows = sum(calls)
        assert calls and rows == len(calls) * runtime.channels
        assert 0 < c["map.rows_wanted"] <= rows
        assert 0 <= c["map.rows_voted"] <= rows
        index_bytes = sum(
            a.nbytes
            for a in runtime.mapper.index.device_arrays().values())
        assert c["map.upload_bytes"] == index_bytes * len(calls)
        # the operator reads them in report()
        assert report["map.upload_bytes"] == c["map.upload_bytes"]

    def test_profiler_trace_holds_the_stages_unwrapped(self, tmp_path):
        """A plain jax.profiler trace of one map-bearing tick carries the
        program's stage spans: no benchmark wrapper is installed."""
        sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
        from bench.lib import trace as trace_lib
        runtime = self._runtime()
        runtime.warmup()
        tel = runtime.telemetry
        assert "stage" not in vars(tel)     # Telemetry.stage itself
        while "map" not in tel.stage_s:
            assert runtime.tick()
        jax.profiler.start_trace(str(tmp_path))
        try:
            before = tel.stage_s["map"]
            while tel.stage_s["map"] == before:
                assert runtime.tick()
        finally:
            jax.profiler.stop_trace()
        tr = trace_lib.load(trace_lib.find_xplane(str(tmp_path)))
        names = {n for n, _, _ in tr.host}
        assert {"stage.map", "stage.map.upload", "stage.map.search",
                "stage.map.vote", "stage.map.extend", "stage.basecall",
                "stage.basecall.wait"} <= names
        spans = {n: (s, e) for n, s, e in tr.host}
        lo, hi = spans["stage.map"]
        for name in self.MAP_PHASES:
            s, e = spans[f"stage.{name}"]
            assert lo <= s <= e <= hi, name


# --------------------------------------------------- lane recycling (CTC) --
class TestLaneRecycleCTC:
    """A recycled lane must start its successor read from a clean slate:
    prev_class back to BLANK (or the first base of the new read can be
    swallowed by the CTC collapse) and zero conv carries (or the ejected
    read's final samples leak into the successor's first frames)."""

    def test_stream_carry_swallows_repeat_without_reset(self):
        # read A ended on class 2; read B opens with class 2.  Without the
        # BLANK reset the collapse drops B's first base — with it, B keeps it.
        logits = jnp.zeros((1, 4, 5)).at[:, :2, 2].set(10.0) \
                                     .at[:, 2:, 3].set(10.0)
        stale_prev = jnp.asarray([2], jnp.int32)        # carry from read A
        tk, ln, _ = ctc.greedy_decode_stream(logits, stale_prev)
        assert ln.tolist() == [1] and tk[0, 0] == 3     # 'C' swallowed
        fresh_prev = jnp.asarray([ctc.BLANK], jnp.int32)
        tk, ln, _ = ctc.greedy_decode_stream(logits, fresh_prev)
        assert ln.tolist() == [2]
        assert tk[0, :2].tolist() == [2, 3]             # 'C' then 'G'

    def _runtime(self, rng, channels=2):
        cfg = bc.BasecallerConfig(kernels=(5, 3), channels=(16, 5),
                                  strides=(1, 2))
        params = bc.init(jax.random.key(0), cfg)
        panel = TargetPanel.build(G.random_genome(rng, 4_000), [(0, 1_000)])
        policy = PolicyConfig(min_prefix_bases=16, map_prefix_bases=24,
                              max_prefix_bases=48, eject_latency_samples=32)
        return AdaptiveSamplingRuntime(
            params, cfg, PrefixMapper(panel), policy, channels=channels,
            chunk_samples=128)

    def test_reset_lanes_zeroes_every_pytree_leaf(self, rng):
        runtime = self._runtime(rng)
        runtime.submit(SimulatedRead(
            signal=rng.normal(size=300).astype(np.float32), read_id=0))
        runtime.tick()   # pollute lane 0 mid-read: carries + counters live
        state = runtime.lane_state
        assert any(np.abs(np.asarray(s[0])).sum() > 0 for s in state["conv"])
        assert int(np.asarray(state["bases"])[0]) >= 0
        assert int(np.asarray(state["ticks"])[0]) == 1
        runtime._reset_lanes([0])
        state = runtime.lane_state
        for leaf in jax.tree.leaves(state):
            np.testing.assert_array_equal(np.asarray(leaf[0]),
                                          np.zeros_like(leaf[0]))
        assert int(np.asarray(state["prev_class"])[0]) == ctc.BLANK

    def test_recycled_lane_matches_fresh_runtime(self, rng):
        """End-to-end recycle oracle: the bases a successor read gets on a
        just-recycled lane equal the bases it gets on a virgin runtime —
        no sample leak, no swallowed first base."""
        sig_a = rng.normal(size=640).astype(np.float32)
        sig_b = rng.normal(size=640).astype(np.float32)
        recycled = self._runtime(np.random.default_rng(5), channels=1)
        recycled.submit_all([
            SimulatedRead(signal=sig_a, read_id=0),
            SimulatedRead(signal=sig_b, read_id=1)])
        recycled.run(max_ticks=200)
        assert len(recycled.records) == 2
        fresh = self._runtime(np.random.default_rng(5), channels=1)
        fresh.submit(SimulatedRead(signal=sig_b, read_id=1))
        fresh.run(max_ticks=200)
        rec_b = [r for r in recycled.records if r.read_id == 1]
        frs_b = [r for r in fresh.records if r.read_id == 1]
        assert len(rec_b) == 1 and len(frs_b) == 1
        assert rec_b[0].decision == frs_b[0].decision
        assert rec_b[0].reason == frs_b[0].reason
        assert rec_b[0].bases_at_decision == frs_b[0].bases_at_decision
        assert rec_b[0].mapped_pos == frs_b[0].mapped_pos

    def test_mid_chunk_recycle_isolates_successor(self, rng):
        """Eject mid-chunk (final partial chunk zero-filled): the successor
        on the same lane decodes identically to a fresh single-lane stream
        of the same read."""
        cfg = bc.BasecallerConfig(kernels=(5, 3), channels=(16, 5),
                                  strides=(1, 2))
        params = bc.init(jax.random.key(0), cfg)
        chunk = 64
        # read A is shorter than one chunk: its lane sees zero-fill + pad
        # frames, then is recycled for read B mid-stream
        sig_a = rng.normal(size=40).astype(np.float32)
        sig_b = rng.normal(size=128).astype(np.float32)
        state = bc.init_stream_state(cfg, 1)
        prev = jnp.full((1,), ctc.BLANK, jnp.int32)
        pads = np.zeros((1, chunk // cfg.total_stride), np.float32)
        pads_a = pads.copy()
        pads_a[0, len(sig_a) // cfg.total_stride:] = 1.0
        rows = np.zeros((1, chunk), np.float32)
        rows[0, :len(sig_a)] = sig_a
        y_a, state = bc.apply_stream(params, state, jnp.asarray(rows), cfg,
                                     fabric="reference")
        _, _, prev = ctc.greedy_decode_stream(y_a, prev, jnp.asarray(pads_a))
        # read A's padded tail forced its frames to BLANK
        assert int(np.asarray(prev)[0]) == ctc.BLANK
        # recycle the lane exactly as the runtime does
        state = [s.at[jnp.asarray([0])].set(0) for s in state]
        prev = prev.at[0].set(ctc.BLANK)
        got = []
        for lo in (0, 64):
            y, state = bc.apply_stream(
                params, state, jnp.asarray(sig_b[None, lo:lo + 64]), cfg,
                fabric="reference")
            tk, ln, prev = ctc.greedy_decode_stream(y, prev,
                                                    jnp.asarray(pads))
            got.extend(np.asarray(tk[0][: int(ln[0])]).tolist())
        # oracle: virgin single-lane stream over read B
        state2 = bc.init_stream_state(cfg, 1)
        prev2 = jnp.full((1,), ctc.BLANK, jnp.int32)
        want = []
        for lo in (0, 64):
            y, state2 = bc.apply_stream(
                params, state2, jnp.asarray(sig_b[None, lo:lo + 64]), cfg,
                fabric="reference")
            tk, ln, prev2 = ctc.greedy_decode_stream(y, prev2,
                                                     jnp.asarray(pads))
            want.extend(np.asarray(tk[0][: int(ln[0])]).tolist())
        assert got == want
