"""Flowcell-scale runtime: lane-invariance golden tests + simulator physics.

The shard_map/lane-pytree refactor of the Read-Until runtime is only safe
if the per-read outcome is a function of the read alone — never of how many
lanes serve the flowcell, how those lanes are meshed over devices, or
whether host admission is double-buffered against device compute.  These
tests pin that: a fixed-seed flowcell must produce identical per-read
decisions (accept/eject + reason + evidence size) across lane counts,
pipeline depths, execution targets, and 1- vs 2-device meshes, with the
1-lane run as the sequential oracle.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import repro.engine as engine_api
from repro.core import basecaller as bc
from repro.core import ctc
from repro.data import flowcell as fc
from repro.data import genome as G
from repro.realtime import Decision, PolicyConfig

SEED = 3
GENOME_LEN = 6_000


def _reference():
    return G.random_genome(np.random.default_rng(7), GENOME_LEN)


def _engine(lanes, *, n_reads=24, pipeline_depth=1, fabric="reference",
            mesh=None, targets=((0, GENOME_LEN // 2),), min_mapq=4.0,
            timeout_decision=Decision.ACCEPT, fused=None):
    return engine_api.build(
        "adaptive_sampling", channels=lanes, chunk=64,
        reference=_reference(), targets=list(targets),
        flowcell={"encoder": "step", "n_reads": n_reads,
                  "read_len": (64, 128), "recovery_samples": 64,
                  "stagger_samples": 16, "seed": SEED},
        policy=PolicyConfig(min_prefix_bases=24, map_prefix_bases=32,
                            max_prefix_bases=96, min_mapq=min_mapq,
                            timeout_decision=timeout_decision,
                            eject_latency_samples=32),
        fabric=fabric, mesh=mesh, pipeline_depth=pipeline_depth,
        fused=fused)


def _golden(engine):
    """Per-read outcome tuple, ordered by arrival rank."""
    recs = sorted(engine.records, key=lambda r: r.read_id)
    return [(r.read_id, r.decision.value, r.reason, r.bases_at_decision,
             r.mapped_pos) for r in recs]


# ------------------------------------------------------- step encoding ----
class TestStepEncoder:
    def test_decodes_exactly(self, rng):
        cfg, params = fc.step_basecaller()
        seq = rng.integers(1, 5, size=96).astype(np.int32)
        sig = fc.step_encode(seq)
        assert len(sig) == 96 * fc.STEP_SAMPLES_PER_BASE
        logits = bc.apply(params, sig[None, :], cfg, padding="stream",
                          fabric="reference")
        tokens, lens = ctc.greedy_decode(logits)
        got = np.asarray(tokens[0][: int(lens[0])])
        np.testing.assert_array_equal(got, seq)

    def test_decodes_exactly_streamed(self, rng):
        """Chunked decode through the streaming state equals the sequence —
        the oracle property every flowcell test below leans on."""
        cfg, params = fc.step_basecaller()
        seq = rng.integers(1, 5, size=64).astype(np.int32)
        sig = fc.step_encode(seq)
        import jax.numpy as jnp
        state = bc.init_stream_state(cfg, 1)
        prev = jnp.full((1,), ctc.BLANK, jnp.int32)
        got = []
        for lo in range(0, len(sig), 64):
            y, state = bc.apply_stream(params, state, sig[None, lo:lo + 64],
                                       cfg, fabric="reference")
            tk, ln, prev = ctc.greedy_decode_stream(y, prev)
            got.extend(np.asarray(tk[0][: int(ln[0])]).tolist())
        assert got == seq.tolist()


# ----------------------------------------------------------- simulator ----
class TestFlowcellSimulator:
    def _sim(self, **kw):
        cfg = fc.FlowcellConfig(channels=4, n_reads=8, read_len=(20, 40),
                                recovery_samples=100, stagger_samples=50,
                                encoder="step", seed=SEED, **kw)
        return fc.FlowcellSimulator(_reference(), cfg)

    def test_stagger_gates_first_capture(self):
        sim = self._sim()
        assert sim.next_read(3, 0) is None          # ready at 3*50
        assert sim.next_read(0, 0) is not None      # ready at 0
        assert sim.next_read(3, 149) is None
        assert sim.next_read(3, 150) is not None

    def test_arrival_order_is_global(self):
        sim = self._sim()
        r0 = sim.next_read(2, 1_000)
        r1 = sim.next_read(0, 1_000)
        assert (r0.read_id, r1.read_id) == (0, 1)

    def test_recovery_holds_channel(self):
        sim = self._sim()
        assert sim.next_read(0, 0) is not None
        sim.read_done(0, 500, hold_samples=40)      # busy until 500+40+100
        assert sim.next_read(0, 639) is None
        assert sim.next_read(0, 640) is not None

    def test_read_content_keyed_on_read_id(self):
        """Molecule i is the same molecule regardless of which channel
        captures it or when — the lane-invariance bedrock."""
        a, b = self._sim(), self._sim()
        ra = [a.next_read(0, 10_000) for _ in range(8)]
        rb = [b.next_read(ch % 4, 10_000) for ch in range(8)]
        for x, y in zip(ra, rb):
            assert x.read_id == y.read_id
            assert x.position == y.position
            np.testing.assert_array_equal(x.signal, y.signal)
        assert a.exhausted and a.next_read(0, 10**9) is None

    def test_pore_encoder_reads_are_normalized(self):
        cfg = fc.FlowcellConfig(channels=2, n_reads=2, read_len=(50, 60),
                                encoder="pore", seed=SEED)
        sim = fc.FlowcellSimulator(_reference(), cfg)
        r = sim.next_read(0, 0)
        assert abs(float(np.median(r.signal))) < 0.2
        assert r.signal.dtype == np.float32

    def test_rejects_bad_config(self):
        with pytest.raises(ValueError):
            fc.FlowcellSimulator(_reference(),
                                 fc.FlowcellConfig(encoder="nope"))
        with pytest.raises(ValueError):
            fc.FlowcellSimulator(np.ones(10, np.int32),
                                 fc.FlowcellConfig(read_len=(20, 40)))


# ------------------------------------------------------ lane invariance ---
class TestLaneInvariance:
    def test_decisions_invariant_under_lane_count(self):
        """8- and 32-lane flowcells reproduce the 1-lane sequential oracle
        read for read: same decision, reason, evidence size, position."""
        oracle = _engine(1)
        oracle.drain(max_steps=20_000)
        golden = _golden(oracle)
        assert len(golden) == 24
        # non-degenerate: the fixed seed exercises both actions via mapping
        decisions = {g[1] for g in golden}
        reasons = {g[2] for g in golden}
        assert "accept" in decisions and "eject" in decisions
        assert "mapped" in reasons
        for lanes in (8, 32):
            eng = _engine(lanes)
            eng.drain(max_steps=20_000)
            assert _golden(eng) == golden, f"lanes={lanes} diverged"

    def test_decisions_invariant_under_double_buffering(self):
        """pipeline_depth=2 decides on identical evidence one tick later:
        decisions/reasons match depth=1 exactly; a deciding lane streams at
        most one extra chunk before the outcome lands."""
        sync = _engine(8)
        sync.drain(max_steps=20_000)
        piped = _engine(8, pipeline_depth=2)
        piped.drain(max_steps=20_000)
        assert _golden(piped) == _golden(sync)
        by_id = {r.read_id: r for r in sync.records}
        for r in piped.records:
            lag = r.samples_at_decision - by_id[r.read_id].samples_at_decision
            assert 0 <= lag <= 64

    def test_decisions_invariant_under_interpret_target(self):
        """pallas_interpret placement (kernel path or counted fallback)
        produces the same decisions as the reference target."""
        ref = _engine(8, n_reads=12)
        ref.drain(max_steps=20_000)
        interp = _engine(8, n_reads=12, fabric="pallas_interpret")
        interp.drain(max_steps=20_000)
        assert _golden(interp) == _golden(ref)

    def test_lane_counters_match_host_sessions(self):
        """The sharded per-lane `bases` counter (the decision loop's prefix
        length) agrees with the host-side session bookkeeping."""
        eng = _engine(8, n_reads=8)
        while eng.step():
            for b, s in enumerate(eng.scheduler.active):
                if s is not None:
                    assert int(np.asarray(
                        eng.runtime.lane_state["bases"])[b]) == len(s.bases)
        eng.runtime.flush()
        assert eng.telemetry.completed == 8


# ------------------------------------------------------- fused invariance --
class TestFusedFlowcell:
    """The fused persistent step (one dispatch for conv→CTC→policy inputs)
    must be invisible to the per-read outcome: fused goldens equal unfused
    goldens at every lane count, under double-buffering, and on the
    interpret target — while collapsing the basecall path to one dispatch
    per tick."""

    def test_fused_goldens_match_unfused(self):
        base = _engine(8)
        base.drain(max_steps=20_000)
        golden = _golden(base)
        for lanes in (1, 8, 32):        # 1 lane: counted fallback path
            eng = _engine(lanes, fused=True)
            eng.drain(max_steps=20_000)
            assert _golden(eng) == golden, f"fused lanes={lanes} diverged"

    def test_fused_goldens_match_under_double_buffering(self):
        sync = _engine(8, pipeline_depth=2)
        sync.drain(max_steps=20_000)
        piped = _engine(8, pipeline_depth=2, fused=True)
        piped.drain(max_steps=20_000)
        assert _golden(piped) == _golden(sync)

    def test_fused_interpret_matches_reference(self):
        ref = _engine(8, n_reads=12, fused=True)
        ref.drain(max_steps=20_000)
        interp = _engine(8, n_reads=12, fabric="pallas_interpret",
                         fused=True)
        interp.drain(max_steps=20_000)
        assert _golden(interp) == _golden(ref)

    def test_fused_collapses_basecall_dispatches(self):
        """Unfused: conv1d + matmul dispatches every tick.  Fused: exactly
        one fused_stream dispatch per tick, zero conv1d/matmul."""
        from repro.kernels import fabric

        def _dispatches(fused):
            eng = _engine(8, n_reads=12, fused=fused)
            base = fabric.counters()
            eng.drain(max_steps=20_000)
            delta = fabric.counters_delta(base)
            by_op = {}
            for k, v in delta.items():
                if k.startswith("fabric.dispatch."):
                    by_op[k.split(".")[2]] = by_op.get(k.split(".")[2], 0) + v
            return by_op, eng.runtime._ticks

        unfused, _ = _dispatches(False)
        fused, ticks = _dispatches(True)
        assert unfused.get("conv1d", 0) > 0
        assert unfused.get("matmul", 0) > 0
        assert fused.get("conv1d", 0) == 0
        assert fused.get("matmul", 0) == 0
        # one dispatch per tick, plus the single warmup trace
        assert fused["fused_stream"] == ticks + 1

    def test_flowcell_512_preset_opts_in(self):
        presets = engine_api.presets("adaptive_sampling")
        assert presets["flowcell_512"]["fused"] is True
        assert presets["edge_int8"]["fused"] is True


# ------------------------------------------------------- mesh invariance --
_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json, sys
sys.path.insert(0, {src!r})
sys.path.insert(0, {tests!r})
import numpy as np
from test_flowcell import _engine, _golden

out = {{}}
for mesh in (None, 1, 2):
    eng = _engine(8, n_reads=12, mesh=mesh)
    eng.drain(max_steps=20_000)
    out[str(mesh)] = {{"golden": _golden(eng)}}
    fused = _engine(8, n_reads=12, mesh=mesh, fused=True)
    fused.drain(max_steps=20_000)
    out[str(mesh)]["fused_golden"] = _golden(fused)

# mesh="auto" trims to the largest device count dividing the lanes: never
# a build error, falls back to unmeshed when nothing divides
from repro.engine.adaptive import resolve_lane_mesh
assert resolve_lane_mesh("auto", 8).size == 2
assert resolve_lane_mesh("auto", 9) is None
print("RESULT " + json.dumps(out))
"""


def test_mesh_invariance_two_devices():
    """1-device and 2-device lane meshes (and the unmeshed runtime) are
    decision-identical on the fixed seed — the shard_map refactor is
    bit-for-bit with the sequential program.  Runs in a subprocess because
    XLA_FLAGS must be set before jax initializes."""
    here = os.path.dirname(__file__)
    src = os.path.abspath(os.path.join(here, "..", "src"))
    script = _MESH_SCRIPT.format(src=src, tests=os.path.abspath(here))
    # the child builds virtual CPU devices: never let it reach for a chip
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    assert out["None"]["golden"] == out["1"]["golden"] == out["2"]["golden"]
    assert len(out["2"]["golden"]) == 12
    # the fused step under every mesh shape matches the unfused goldens
    for mesh in ("None", "1", "2"):
        assert out[mesh]["fused_golden"] == out["None"]["golden"], \
            f"fused mesh={mesh} diverged"


# ------------------------------------------------- flowcell-economy tests --
class TestFlowcellEconomy:
    def test_ejects_buy_throughput(self):
        """With every molecule off-target, an ejecting flowcell finishes the
        same pool in far fewer flowcell ticks than a never-eject one — the
        channel-time economy the pore lifecycle models."""
        eject = _engine(4, targets=((0, 1),), n_reads=16)
        eject.drain(max_steps=20_000)
        hold = _engine(4, targets=((0, 1),), n_reads=16, min_mapq=1e9)
        hold.drain(max_steps=20_000)
        assert eject.summary()["ejected"] == 16
        assert hold.summary()["ejected"] == 0
        assert eject.runtime._ticks < hold.runtime._ticks
        assert (eject.summary()["pore_time_saved_samples"]
                > hold.summary()["pore_time_saved_samples"])

    def test_occupancy_and_flowcell_telemetry(self):
        eng = _engine(8)
        rep = eng.drain(max_steps=20_000)
        assert rep["reads"] == 24
        assert 0.0 < rep["occupancy_mean"] <= 1.0
        assert rep["occupancy_min"] <= rep["occupancy_mean"] \
            <= rep["occupancy_max"] <= 1.0
        assert rep["flowcell_ticks"] == eng.runtime._ticks
        assert rep["flowcell_samples"] == eng.runtime._ticks * 64
        assert rep["pore_time_saved_samples"] == eng.telemetry.samples_saved
        assert rep["reads_per_channel_mean"] == pytest.approx(24 / 8)

    def test_report_counts_match_submitted_after_flush(self):
        """The double-buffered runtime's final in-flight tick is flushed by
        drain(): every submitted read lands in the report, and the latency
        aliases cover every decided read (the report-before-flush bug)."""
        eng = _engine(8, pipeline_depth=2)
        rep = eng.drain(max_steps=20_000)
        assert rep["reads"] == 24
        assert (rep["accepted"] + rep["ejected"] + rep["timeouts"]
                + rep["exhausted"]) == 24
        tel = eng.telemetry
        decided = rep["accepted"] + rep["ejected"] + rep["timeouts"]
        assert len(tel.latencies_ms) == decided
        assert rep["decision_p99_ms"] >= rep["decision_p50_ms"] >= 0.0


# ------------------------------------------------------ engine surface ----
class TestFlowcellEngineSurface:
    def test_flowcell_smoke_preset_builds_step_decoder(self):
        eng = engine_api.build("adaptive_sampling", preset="flowcell_smoke",
                               channels=16,
                               flowcell={"encoder": "step", "n_reads": 16,
                                         "read_len": (48, 64)},
                               fabric="reference")
        assert eng.flowcell is not None
        assert eng.runtime.cfg.kernels == (2, 1)  # step_basecaller attached
        rep = eng.drain()
        assert rep["reads"] == 16

    def test_flowcell_512_preset_registered(self):
        presets = engine_api.presets("adaptive_sampling")
        assert presets["flowcell_512"]["channels"] == 512
        assert presets["flowcell_512"]["flowcell"]["encoder"] == "step"

    def test_queue_fed_runtime_is_one_lane_flowcell_alias(self):
        """Without a flowcell source the engine serves its submit queue on
        the same lane-pytree tick loop (the documented migration: channels=N
        now aliases a 1-device flowcell lane pool)."""
        cfg, params = fc.step_basecaller()
        ref = _reference()
        eng = engine_api.build("adaptive_sampling", params=params, cfg=cfg,
                               reference=ref, targets=[(0, GENOME_LEN // 2)],
                               channels=4, chunk=64,
                               policy=PolicyConfig(min_prefix_bases=24,
                                                   map_prefix_bases=32,
                                                   max_prefix_bases=96,
                                                   eject_latency_samples=32),
                               fabric="reference")
        assert eng.flowcell is None
        for i in range(6):
            start = 500 + 700 * i
            eng.submit(fc.step_encode(ref[start:start + 80]), read_id=i,
                       on_target=start + 40 < GENOME_LEN // 2)
        rep = eng.drain()
        assert rep["reads"] == 6
        assert rep["accepted"] + rep["ejected"] == 6
