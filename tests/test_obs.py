"""Observability stack: bounded/mergeable metrics, span tracing, scoped
fabric attribution, time-series export.

Pinned behaviours:

  * ``LogHistogram`` is bit-exact vs the ``weighted_percentile`` oracle in
    exact mode, within one bucket width after folding, O(buckets) memory
    past ``exact_until``, and merge-associative (satellite: the unbounded
    ``latencies_ms`` list fix).
  * ``Telemetry.summary()`` namespaces counter/gauge keys that would
    shadow reserved scalars instead of silently replacing them
    (satellite: the key-collision hazard).
  * Two engines interleaving in one process each report exactly their own
    fabric dispatches (satellite: scoped counters replace the process-wide
    baseline delta).
  * Exported Chrome traces validate (matched B/E, monotone ts, named
    pids), carry >= one complete read span per submitted read correlated
    by read_id, and the disabled tracer records nothing.
"""
import io
import json

import jax
import numpy as np
import pytest

from optional_hypothesis import given, settings, st

from repro.engine.telemetry import Telemetry
from repro.kernels import fabric as fabric_mod
from repro.obs import (Counters, Gauges, LogHistogram, NULL_TRACER, Tracer,
                       TimeSeriesExporter, as_tracer, jax_profile_window,
                       validate_chrome_trace, weighted_percentile)
from repro.obs.export import validate_timeseries
from repro.obs.trace import _NULL_SPAN, read_spans


# ------------------------------------------------------------ histogram ----
class TestLogHistogram:
    def test_exact_mode_matches_oracle_bit_for_bit(self):
        rng = np.random.default_rng(0)
        vals = rng.lognormal(2.0, 1.5, size=500)
        wts = rng.integers(1, 9, size=500).astype(float)
        h = LogHistogram()
        for v, w in zip(vals, wts):
            h.observe(v, w)
        assert not h.folded
        for q in (0, 10, 50, 90, 99, 100):
            assert h.percentile(q) == weighted_percentile(vals, wts, q)

    def test_folded_percentiles_within_one_bucket_of_oracle(self):
        rng = np.random.default_rng(1)
        vals = rng.lognormal(3.0, 2.0, size=10_000)
        wts = rng.integers(1, 5, size=10_000).astype(float)
        h = LogHistogram(exact_until=256)
        for v, w in zip(vals, wts):
            h.observe(v, w)
        assert h.folded
        bound = h.relative_error_bound()
        for q in (50, 99):
            exact = weighted_percentile(vals, wts, q)
            got = h.percentile(q)
            assert abs(got - exact) <= bound * exact + 1e-12, (q, got, exact)

    def test_memory_stays_o_buckets_after_fold(self):
        h = LogHistogram(exact_until=64)
        for i in range(10_000):
            h.observe(0.1 + (i % 997), 1.0 + (i % 3))
        assert h.folded
        # raw storage is gone; the bucket array never grows with n
        assert h.values == [] and h.weights == []
        assert len(h.counts) == h.n_buckets + 2
        assert h.n == 10_000

    def test_merge_associative_across_merge_trees(self):
        rng = np.random.default_rng(2)
        shards = [rng.lognormal(1.0, 1.0, size=300) for _ in range(3)]

        def hist(values):
            h = LogHistogram(exact_until=100)   # every shard folds
            for v in values:
                h.observe(v)
            return h

        a, b, c = (hist(s) for s in shards)
        left = hist(shards[0]).merge(hist(shards[1])).merge(hist(shards[2]))
        right = hist(shards[0]).merge(hist(shards[1]).merge(hist(shards[2])))
        assert np.array_equal(left.counts, right.counts)
        assert left.n == right.n == sum(len(s) for s in shards)
        for q in (10, 50, 99):
            assert left.percentile(q) == right.percentile(q)

    def test_merge_exact_histograms_stays_exact_under_window(self):
        h1, h2 = LogHistogram(), LogHistogram()
        for v in (1.0, 2.0):
            h1.observe(v)
        for v in (3.0, 4.0):
            h2.observe(v)
        h1.merge(h2)
        assert not h1.folded
        assert h1.percentile(50) == weighted_percentile(
            [1, 2, 3, 4], [1, 1, 1, 1], 50)

    def test_incompatible_layouts_refuse_to_merge(self):
        with pytest.raises(ValueError):
            LogHistogram(growth=2.0).merge(LogHistogram(growth=1.5))

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.floats(min_value=1e-3, max_value=1e6,
                              allow_nan=False, allow_infinity=False),
                    min_size=1, max_size=200),
           st.integers(min_value=1, max_value=50))
    def test_property_fold_error_bounded(self, values, exact_until):
        h = LogHistogram(exact_until=exact_until)
        for v in values:
            h.observe(v)
        for q in (0, 50, 100):
            exact = weighted_percentile(values, [1.0] * len(values), q)
            assert abs(h.percentile(q) - exact) \
                <= h.relative_error_bound() * exact + 1e-9

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.floats(min_value=1e-3, max_value=1e6,
                              allow_nan=False, allow_infinity=False),
                    min_size=2, max_size=100),
           st.integers(min_value=1, max_value=99))
    def test_property_merge_order_invariant(self, values, cut):
        cut = cut % (len(values) - 1) + 1

        def hist(vs):
            h = LogHistogram(exact_until=8)
            for v in vs:
                h.observe(v)
            return h

        ab = hist(values[:cut]).merge(hist(values[cut:]))
        ba = hist(values[cut:]).merge(hist(values[:cut]))
        for q in (25, 50, 75):
            assert ab.percentile(q) == ba.percentile(q)


# ------------------------------------------------- counters and gauges ----
class TestCountersGauges:
    def test_counters_merge_sums(self):
        a = Counters({"x": 2, "y": 1})
        b = Counters({"x": 3, "z": 5})
        assert a.merge(b) == {"x": 5, "y": 1, "z": 5}

    def test_gauges_merge_keeps_freshest_write(self):
        g1, g2 = Gauges(), Gauges()
        g1["occ"] = 0.5
        g2["occ"] = 0.9          # written later -> fresher
        assert g1.merge(g2)["occ"] == 0.9

        g3, g4 = Gauges(), Gauges()
        g4["occ"] = 0.9
        g3["occ"] = 0.5          # g3's write is now the fresher one
        assert g3.merge(g4)["occ"] == 0.5


# ----------------------------------------------------- telemetry facade ----
class TestTelemetrySummary:
    def test_counter_colliding_with_scalar_is_namespaced(self):
        tel = Telemetry("w")
        tel.steps = 7
        tel.count("steps", 3)            # workload counter, same name
        tel.count("accepted", 2)         # non-colliding stays flat
        s = tel.summary()
        assert s["steps"] == 7           # scalar untouched
        assert s["counters.steps"] == 3  # collision namespaced, not lost
        assert s["accepted"] == 2

    def test_gauge_colliding_with_scalar_is_namespaced(self):
        tel = Telemetry("w")
        tel.wall_s = 1.5
        tel.gauge("wall_s", 99.0)
        s = tel.summary()
        assert s["wall_s"] == 1.5
        assert s["gauges.wall_s"] == 99.0

    def test_latency_list_accessors_backward_compatible(self):
        tel = Telemetry("w")
        tel.observe_latency(5.0, weight=4.0)
        tel.observe_latency(9.0, weight=4.0)
        assert tel.latencies_ms == [5.0, 9.0]
        assert tel.latency_weights == [4.0, 4.0]
        assert tel.latency_percentile(50) == 5.0

    def test_merge_rolls_up_fleet_view(self):
        a, b = Telemetry("w"), Telemetry("w")
        a.wall_s, b.wall_s = 2.0, 3.0            # concurrent engines
        a.completed, b.completed = 4, 6
        a.observe_latency(1.0)
        b.observe_latency(9.0)
        a.count("accepted", 1)
        b.count("accepted", 2)
        a.merge(b)
        assert a.wall_s == 3.0                   # max, not sum
        assert a.completed == 10
        assert a.counters["accepted"] == 3
        assert a.latency_hist.n == 2


# --------------------------------------------- scoped fabric attribution ----
class TestScopedFabricAttribution:
    def _engine(self):
        import repro.engine as engine_api
        return engine_api.build("basecall", preset="smoke",
                                fabric="reference", seed=0)

    def _rows(self, n=8):
        rng = np.random.default_rng(3)
        return rng.normal(size=(n, 512)).astype(np.float32)

    def test_two_interleaved_engines_attribute_exactly(self):
        # the process-wide-delta hazard this replaces: engine A's "delta
        # since my last read" silently absorbed engine B's dispatches.
        # Exactness oracle: a solo engine run on the same inputs.
        rows = self._rows()
        solo = self._engine()
        solo.submit(rows)
        while solo.step():
            pass
        want = solo.telemetry.fabric_counters()
        assert any(k.startswith("fabric.dispatch.") for k in want), want

        a, b = self._engine(), self._engine()
        a.submit(rows)
        b.submit(rows)
        progressed = True
        while progressed:                        # strict interleaving
            progressed = a.step()
            progressed = b.step() or progressed
        assert a.telemetry.fabric_counters() == want
        assert b.telemetry.fabric_counters() == want

    def test_scope_is_reentrant_no_double_count(self):
        tel = Telemetry("w")
        with tel.scope(), tel.scope():
            fabric_mod.note("matmul", "reference")
        assert tel.fabric_counters()["fabric.dispatch.matmul.reference"] == 1

    def test_unscoped_bumps_do_not_leak_into_engines(self):
        tel = Telemetry("w")
        fabric_mod.note("matmul", "reference")   # outside any scope
        assert tel.fabric_counters() == {}


# --------------------------------------------------------------- tracer ----
class TestTracer:
    def test_disabled_tracer_records_nothing(self):
        t = Tracer(enabled=False)
        pid = t.pid("engine")
        tid = t.tid(pid, "host")
        t.begin("read", pid=pid, tid=tid)
        t.end(pid=pid, tid=tid)
        t.instant("x", pid=pid, tid=tid)
        t.counter("c", {"v": 1}, pid=pid)
        with t.span("s", pid=pid, tid=tid):
            pass
        doc = t.to_chrome()
        assert doc["traceEvents"] == []
        assert t.scheduler_hook(pid) is None
        assert t.fabric_hook(pid) is None
        # the hot path hands out one shared null context manager
        assert t.span("s", pid=pid, tid=tid) is _NULL_SPAN
        assert as_tracer(False) is NULL_TRACER
        assert as_tracer(None) is NULL_TRACER
        assert as_tracer(t) is t

    def test_matched_spans_validate_and_correlate(self):
        t = Tracer()
        pid = t.pid("engine")
        lane = t.tid(pid, "lane000")
        t.begin("read", pid=pid, tid=lane, args={"read_id": 7})
        t.instant("tick.dispatch", pid=pid, tid=t.tid(pid, "host"))
        t.end(pid=pid, tid=lane, args={"decision": "EJECT"})
        doc = t.to_chrome()
        assert validate_chrome_trace(doc) == []
        spans = read_spans(doc)
        assert len(spans) == 1
        assert spans[0]["read_id"] == 7
        assert spans[0]["args"]["decision"] == "EJECT"
        assert spans[0]["dur_us"] >= 0

    def test_open_span_closed_at_export(self):
        t = Tracer()
        pid = t.pid("engine")
        tid = t.tid(pid, "lane000")
        t.begin("read", pid=pid, tid=tid, args={"read_id": 0})
        doc = t.to_chrome()
        assert validate_chrome_trace(doc) == []
        (span,) = read_spans(doc)
        assert span["args"]["open_at_export"] is True

    def test_dropped_begin_suppresses_its_end(self):
        t = Tracer(max_events=2)
        pid = t.pid("engine")
        tid = t.tid(pid, "lane000")
        for i in range(5):                       # 3 of these 5 B's drop
            t.begin("read", pid=pid, tid=tid, args={"read_id": i})
        for _ in range(5):
            t.end(pid=pid, tid=tid)
        assert t.dropped == 3
        doc = t.to_chrome()
        assert validate_chrome_trace(doc) == []  # no unmatched E
        assert len(read_spans(doc)) == 2

    def test_stage_records_x_span(self):
        tel = Telemetry("w", tracer=True)
        with tel.stage("map"):
            pass
        xs = [e for e in tel.tracer.to_chrome()["traceEvents"]
              if e.get("ph") == "X"]
        assert [e["name"] for e in xs] == ["map"]
        assert xs[0]["dur"] >= 0
        assert tel.stage_s["map"] >= 0

    def test_nested_stages_keep_inclusive_time(self):
        tel = Telemetry("w", tracer=True)
        with tel.stage("map"):
            with tel.stage("map.upload"):
                pass
            with tel.stage("map.vote"):
                pass
        st = tel.stage_s
        assert st["map.upload"] + st["map.vote"] <= st["map"]
        xs = [e for e in tel.tracer.to_chrome()["traceEvents"]
              if e.get("ph") == "X"]
        assert [e["name"] for e in xs] == ["map", "map.upload", "map.vote"]
        assert validate_chrome_trace(tel.tracer.to_chrome()) == []

    def test_stage_opens_a_profiler_annotation(self, monkeypatch):
        """Every stage runs under ``TraceAnnotation("stage.<name>")``, so
        any profiler trace carries it, with or without a Tracer."""
        opened = []

        class Recording:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                opened.append(("enter", self.name))

            def __exit__(self, *exc):
                opened.append(("exit", self.name))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
        tel = Telemetry("w")
        with tel.stage("basecall"):
            with tel.stage("basecall.wait"):
                pass
        assert opened == [("enter", "stage.basecall"),
                          ("enter", "stage.basecall.wait"),
                          ("exit", "stage.basecall.wait"),
                          ("exit", "stage.basecall")]

    def test_duplicate_process_labels_disambiguate(self):
        t = Tracer()
        assert t.pid("basecall") != t.pid("basecall")
        names = [m["args"]["name"] for m in t.meta
                 if m["name"] == "process_name"]
        assert len(set(names)) == 2


# ------------------------------------------------------ engine trace e2e ----
class TestEngineTraceEndToEnd:
    def test_adaptive_engine_trace_has_one_span_per_read(self, tmp_path):
        import repro.engine as engine_api
        n_reads = 6
        eng = engine_api.build("adaptive_sampling", preset="smoke",
                               trace=True)
        rng = np.random.default_rng(0)
        for i in range(n_reads):
            eng.submit(rng.normal(size=8 * eng.runtime.chunk_samples
                                  ).astype(np.float32),
                       read_id=i, on_target=bool(i % 2))
        eng.drain()
        path = tmp_path / "trace.json"
        doc = eng.telemetry.tracer.export_chrome(str(path))
        assert validate_chrome_trace(doc) == []
        assert validate_chrome_trace(json.loads(path.read_text())) == []
        spans = read_spans(doc)
        assert len(spans) >= n_reads
        assert {s["read_id"] for s in spans} == set(range(n_reads))
        for s in spans:                          # every span fully decided
            assert s["args"]["decision"] in ("ACCEPT", "EJECT")
            assert s["dur_us"] > 0
        # stage spans + scheduler instants landed on the same process
        phases = {e["ph"] for e in doc["traceEvents"]}
        assert {"B", "E", "X", "i", "C", "M"} <= phases

    def test_offline_step_times_its_readback(self):
        """``BasecallEngine.step`` reads its rows back inside one
        ``readback`` stage a batch, within the step's wall time."""
        import time

        import repro.engine as engine_api
        eng = engine_api.build("basecall", preset="smoke", seed=0)
        eng.submit(np.zeros((4, 512), np.float32))
        t0 = time.perf_counter()
        assert eng.step()
        wall = time.perf_counter() - t0
        st = eng.telemetry.stage_s
        assert 0 < st["readback"] <= wall
        assert st["readback"] + st["basecall"] + st["decode"] <= wall
        assert len(eng.reads) == 4

    def test_untraced_engine_emits_zero_events(self):
        import repro.engine as engine_api
        eng = engine_api.build("basecall", preset="smoke", seed=0)
        eng.submit(np.zeros((4, 512), np.float32))
        eng.drain()
        assert eng.telemetry.tracer is NULL_TRACER
        assert eng.telemetry.tracer.events == []


# ------------------------------------------------------------- exporter ----
class TestTimeSeriesExporter:
    def test_delta_semantics_and_jsonl_schema(self, tmp_path):
        clock = [0.0]
        tel = Telemetry("w")
        path = tmp_path / "ts.jsonl"
        exp = TimeSeriesExporter(tel, interval_s=1.0, path=str(path),
                                 clock=lambda: clock[0])
        tel.exporter = exp

        tel.bases += 100
        tel.steps += 1
        tel.count("accepted", 2)
        clock[0] = 0.5
        tel.tick_export()                 # under the interval: no record
        assert exp.records == []
        clock[0] = 1.0
        tel.tick_export()
        rec = exp.records[-1]
        assert rec["bases_per_s"] == pytest.approx(100.0)
        assert rec["counter_deltas"] == {"accepted": 2}

        clock[0] = 2.0                    # idle interval -> zero rates
        exp.emit()
        assert exp.records[-1]["bases_per_s"] == 0.0
        assert exp.records[-1]["counter_deltas"] == {}
        exp.close()
        assert validate_timeseries(str(path)) == []

    def test_stream_output_is_json_lines(self):
        clock = [0.0]
        buf = io.StringIO()
        tel = Telemetry("w")
        exp = TimeSeriesExporter(tel, interval_s=0.0, stream=buf,
                                 clock=lambda: clock[0])
        tel.bases += 10
        clock[0] = 1.0
        exp.emit()
        (line,) = buf.getvalue().splitlines()
        assert json.loads(line)["bases_per_s"] == pytest.approx(10.0)


class TestJaxProfileWindow:
    def test_no_logdir_is_a_noop(self, monkeypatch):
        def boom(*_):
            raise AssertionError("profiler must not start")
        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        with jax_profile_window(None) as started:
            assert started is False
        with jax_profile_window("unused", enabled=False) as started:
            assert started is False

    def test_failed_start_raises(self, monkeypatch, tmp_path):
        def fail(*_):
            raise RuntimeError("no profiler here")
        monkeypatch.setattr(jax.profiler, "start_trace", fail)
        with pytest.raises(RuntimeError, match="no profiler"):
            with jax_profile_window(str(tmp_path)):
                pass

    def test_failed_stop_raises(self, monkeypatch, tmp_path):
        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace", calls.append)

        def fail():
            raise RuntimeError("trace lost")
        monkeypatch.setattr(jax.profiler, "stop_trace", fail)
        with pytest.raises(RuntimeError, match="trace lost"):
            with jax_profile_window(str(tmp_path)) as started:
                assert started is True
        assert calls == [str(tmp_path)]
