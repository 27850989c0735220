"""Adaptive-sampling (Read-Until) runtime: sense -> basecall -> map -> decide.

The paper's SoC exists to act on nanopore signal *in real time*; the
highest-value real-time workload is selective sequencing: basecall a read's
prefix, map it, and decide within milliseconds whether to keep sequencing
the molecule or eject it and free the pore for the next one.  This module
closes that loop on top of the existing pieces:

  * **stateful chunked basecalling** — ``basecaller.apply_stream`` carries
    each conv layer's K-stride overlap rows across chunk boundaries, so a
    growing read is basecalled incrementally at O(chunk) per tick instead of
    re-running the CNN over the read-so-far;
  * **incremental CTC collapse** — ``ctc.greedy_decode_stream`` carries one
    class per channel across chunks;
  * **on-the-fly mapping** — ``PrefixMapper`` (FM-index seeds + banded
    extension) over fixed-shape batches of the latest called bases;
  * **decision policy** — ``policy.decide`` turns mapping results into
    ACCEPT / EJECT / WAIT; EJECT frees the channel after an eject-latency
    penalty and banks the molecule's remaining signal as saved.

**Flowcell scale.**  All per-lane device state — conv carries, the CTC
``prev_class`` carry, and the per-lane policy counters (bases called, ticks
since reset) — lives in a single pytree (:func:`init_lane_state`) whose
leading axis is the channel lane.  The per-tick compute is one jitted step
(basecall + CTC collapse + counter update) over every lane at once; given a
``mesh`` (see :func:`repro.distributed.sharding.lane_mesh`) the step is
wrapped in ``shard_map`` with lanes sharded across devices and params
replicated — the default single-device runtime is exactly the 1-device
degenerate case of the same program.  Host-side work (admission, sensing,
mapping, decisions) can be double-buffered against device compute with
``pipeline_depth=2``: the tick-t basecall is dispatched asynchronously and
tick t-1's tokens are mapped/decided while it runs.  Decisions and reasons
per read are identical to the synchronous runtime (same evidence, same
rule); the only difference is that a deciding lane streams one extra chunk
before the outcome lands — real Read-Until decision latency.  The pending
in-flight tick is flushed by ``flush()`` (``run``/``drain`` call it) so
telemetry never drops the final partial tick's observations.

A :class:`repro.data.flowcell.FlowcellSimulator` can be attached as
``source``: free channels then poll it for staggered, arrival-ordered reads
(pore lifecycle: sequencing -> ejected -> recovering -> next capture), and
every decision reports back the pore-time the molecule still holds — so
eject decisions genuinely buy channel throughput.  Without a source the
runtime serves its submit queue, which makes a plain
``AdaptiveSamplingRuntime(channels=N)`` the 1-device, queue-fed alias of a
flowcell lane pool.

Channel-lane bookkeeping (admission, recycling) is the shared
:class:`repro.engine.scheduler.SlotScheduler`; accounting is the shared
:class:`repro.engine.telemetry.Telemetry`.  Every device call is
fixed-shape (idle channel lanes are zero-filled and their outputs ignored;
lanes are reset when a new read is assigned), so the jitted step compiles
exactly once per run — the software analogue of the SoC's statically
provisioned MAT/ED engines.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import basecaller as bc
from repro.core import ctc
from repro.engine.scheduler import SlotScheduler
from repro.kernels import fabric as fabric_mod
from repro.engine.telemetry import Telemetry
from repro.realtime import policy as policy_mod
from repro.realtime.mapper import PrefixMapper
from repro.realtime.policy import Decision, PolicyConfig
from repro.realtime.session import ChannelSession, ReadRecord, SimulatedRead


def init_lane_state(cfg: bc.BasecallerConfig, channels: int) -> dict:
    """The per-lane device state pytree, lane-major on every leaf.

    ``conv``        per-layer (lanes, K-stride, Cin) streaming carries
    ``prev_class``  (lanes,) CTC collapse carry (BLANK at read start)
    ``bases``       (lanes,) bases called since lane reset (policy counter)
    ``ticks``       (lanes,) device steps since lane reset

    Every leaf zeroes on lane reset (BLANK == 0), so recycling a lane is one
    scatter over the whole tree; every leaf shards over the lane axis under
    ``shard_map``.
    """
    return {
        "conv": bc.init_stream_state(cfg, channels),
        "prev_class": jnp.full((channels,), ctc.BLANK, jnp.int32),
        "bases": jnp.zeros((channels,), jnp.int32),
        "ticks": jnp.zeros((channels,), jnp.int32),
    }


def build_step_fn(cfg: bc.BasecallerConfig, fabric: fabric_mod.FabricPolicy,
                  mesh=None, fused: bool = False):
    """One jitted tick over all lanes: basecall + CTC collapse + counters.

    ``(params, lane_state, rows, frame_pads) -> (tokens, lens, lane_state')``
    with every argument/result lane-major.  With a mesh, the step runs under
    ``shard_map``: lane-major leaves shard over the lane axis, params
    replicate, and no collectives are needed (lanes are independent) — so
    the sharded program is arithmetically identical to the sequential one.

    ``fused=True`` dispatches the whole chain as the single
    ``"fused_stream"`` fabric op (one lane-major Pallas program — or its
    definitionally-identical reference composition — see
    :mod:`repro.kernels.fused_stream`).  The fused step takes one extra
    lane-major argument, a ``reset`` mask, and folds the recycled-lane
    state zeroing inside the op, so the runtime skips its host-side reset
    scatter; the signature becomes
    ``(params, lane_state, rows, frame_pads, reset) -> ...``.  Under a
    mesh the dispatch happens inside the sharded body, so per-shard lane
    counts drive the kernel/fallback choice (sharding can suppress the
    kernel — counted, never silent).
    """
    if fused:
        from repro.kernels import fused_stream as fs

        def step(params, lane, rows, frame_pads, reset):
            return fs.fused_stream_step(params, lane, rows, frame_pads,
                                        reset, cfg=cfg, fabric=fabric)

        in_specs_tail = 4
    else:
        def step(params, lane, rows, frame_pads):
            logits, conv = bc.apply_stream_core(params, lane["conv"], rows,
                                                cfg=cfg, fabric=fabric)
            tokens, lens, prev = ctc.greedy_decode_stream(
                logits, lane["prev_class"], frame_pads)
            new_lane = {
                "conv": conv,
                "prev_class": prev,
                "bases": lane["bases"] + lens.astype(jnp.int32),
                "ticks": lane["ticks"] + 1,
            }
            return tokens, lens, new_lane

        in_specs_tail = 3

    if mesh is not None:
        from repro.distributed.sharding import LANE_AXIS
        lane_p = P(LANE_AXIS)
        # pytree-prefix specs: one P() replicates the whole params tree, one
        # lane spec shards every lane-major leaf of the state tree.  The
        # Pallas kernels' output shapes carry no varying-axes annotation,
        # so the checker is off.
        step = jax.shard_map(step, mesh=mesh,
                             in_specs=(P(),) + (lane_p,) * in_specs_tail,
                             out_specs=(lane_p, lane_p, lane_p),
                             check_vma=False)
    return jax.jit(step)


class AdaptiveSamplingRuntime:
    """Manages a pool of concurrent channel sessions with streaming state."""

    def __init__(self, params, cfg: bc.BasecallerConfig, mapper: PrefixMapper,
                 policy: PolicyConfig = PolicyConfig(), *, channels: int = 32,
                 chunk_samples: int = 256, use_kernel=fabric_mod.UNSET,
                 fabric=None, mesh=None, pipeline_depth: int = 1,
                 source=None, tracer=None, fused=None):
        if chunk_samples % cfg.total_stride:
            raise ValueError(
                f"chunk_samples={chunk_samples} must be a multiple of the "
                f"basecaller total_stride={cfg.total_stride}")
        if pipeline_depth not in (1, 2):
            raise ValueError(f"pipeline_depth must be 1 or 2, "
                             f"got {pipeline_depth}")
        if mesh is not None and channels % mesh.size:
            raise ValueError(
                f"channels={channels} must divide evenly over the "
                f"{mesh.size}-device lane mesh")
        if source is not None and source.config.channels != channels:
            raise ValueError(
                f"flowcell source has {source.config.channels} channels, "
                f"runtime has {channels}")
        self.params = params
        self.cfg = cfg
        self.mapper = mapper
        self.policy = policy
        self.channels = channels
        self.chunk_samples = chunk_samples
        self.mesh = mesh
        self.pipeline_depth = pipeline_depth
        # basecall placement: fabric policy (``use_kernel=`` is a shim)
        self.fabric = fabric_mod.as_policy(fabric_mod.legacy_policy(
            "AdaptiveSamplingRuntime", use_kernel, fabric=fabric))
        # fused persistent step: explicit True/False wins; None auto-opts in
        # exactly when the policy places the fused op on a Pallas target
        # (so reference-policy runtimes keep the unfused chain and its
        # per-op dispatch telemetry unless a preset/caller opts in)
        if fused is None:
            fused = fabric_mod.select("fused_stream", self.fabric).use_pallas
        self.fused = bool(fused)
        self._step = build_step_fn(cfg, self.fabric, mesh, fused=self.fused)
        self.lane_state = init_lane_state(cfg, channels)
        if mesh is not None:
            # lane-sharded from the start: the step never sees (and never
            # compiles for) state parked on one device
            from jax.sharding import NamedSharding

            from repro.distributed.sharding import LANE_AXIS
            self.lane_state = jax.device_put(
                self.lane_state, NamedSharding(mesh, P(LANE_AXIS)))
        self.records: list[ReadRecord] = []
        self.telemetry = Telemetry(workload="adaptive_sampling",
                                   tracer=tracer)
        self._trace = self.telemetry.tracer
        self._pid = self.telemetry.trace_pid
        # channel lanes: slot = sensor channel, payload = ChannelSession
        self.scheduler = SlotScheduler(
            channels, on_event=self._trace.scheduler_hook(self._pid))
        self._source = source
        self._pending = None            # in-flight tick awaiting map/decide
        self._ticks = 0                 # flowcell time, in chunks (incl idle)
        self._busy_ticks = np.zeros(channels, np.int64)
        self._lane_reads = np.zeros(channels, np.int64)
        self._warm = False

    # -------------------------------------------------- compat aliases --
    @property
    def state(self):
        """Per-layer conv carries (pre-flowcell name; lanes-major)."""
        return self.lane_state["conv"]

    @property
    def prev_class(self):
        return self.lane_state["prev_class"]

    @property
    def flowcell_samples(self) -> int:
        """Flowcell time: every tick advances each channel by one chunk."""
        return self._ticks * self.chunk_samples

    def warmup(self) -> None:
        """Compile every jitted path once, before any session is timed.

        Without this, the first wave of channel sessions absorbs one-time
        JIT compilation into its wall-clock decision latency (observed
        ~100x the steady-state figure), corrupting p50/p99.
        """
        if self._warm:
            return
        rows = jnp.zeros((self.channels, self.chunk_samples), jnp.float32)
        pads = jnp.zeros((self.channels,
                          self.chunk_samples // self.cfg.total_stride),
                         jnp.float32)
        with self.telemetry.scope():
            # per-instance jit traces here, inside this engine's fabric
            # scope: execution-time dispatch counters stay attributed to
            # this runtime even when engines interleave in one process
            if self.fused:
                tokens, _, _ = self._step(
                    self.params, self.lane_state, rows, pads,
                    jnp.zeros((self.channels,), jnp.float32))
            else:
                tokens, _, _ = self._step(self.params, self.lane_state, rows,
                                          pads)
            jax.block_until_ready(tokens)
            self.mapper.map_prefixes(
                np.zeros((self.channels, self.policy.map_prefix_bases),
                         np.int32))
        self._warm = True

    # ------------------------------------------------------------ intake --
    def submit(self, read: SimulatedRead) -> None:
        """Queue a read for the next free lane (queue-fed mode only: a
        source-fed flowcell owns its channels' pore lifecycle, and a
        queue-admitted read would land on a pore the simulator still
        considers recovering and corrupt its ready_at clock)."""
        if self._source is not None:
            raise ValueError(
                "runtime is source-fed (flowcell attached): reads arrive by "
                "pore capture, not submit(); build without flowcell= for "
                "queue-fed serving")
        self.scheduler.submit(read)

    def submit_all(self, reads) -> None:
        for r in reads:
            self.submit(r)

    # ------------------------------------------------------ lane control --
    def _reset_lanes(self, lanes: list[int]) -> None:
        """Zero every lane-state leaf of channels starting a new read: conv
        carries, CTC carry (BLANK == 0), and the per-lane counters."""
        if not lanes:
            return
        idx = jnp.asarray(np.asarray(lanes, np.int32))
        self.lane_state = jax.tree.map(lambda s: s.at[idx].set(0),
                                       self.lane_state)

    def _poll_source(self) -> list[int]:
        """Capture the next arrival-ordered molecule on every recovered
        channel (flowcell mode only); returns the freshly occupied lanes."""
        src = self._source
        if src is None:
            return []
        t = self.flowcell_samples
        now = time.perf_counter()
        active = self.scheduler.active
        fresh = []
        for b in range(self.channels):
            if active[b] is not None:
                continue
            read = src.next_read(b, t)
            if read is None:
                continue
            self.scheduler.assign(b, ChannelSession(channel=b, read=read,
                                                    started_wall=now))
            fresh.append(b)
        return fresh

    def _assign_free(self) -> list[int]:
        now = time.perf_counter()
        fresh = self.scheduler.admit(
            wrap=lambda b, read: ChannelSession(channel=b, read=read,
                                                started_wall=now))
        return [b for b, _ in fresh]

    # ------------------------------------------------------------ tracing --
    def _lane_tid(self, b: int) -> int:
        return self._trace.tid(self._pid, f"lane{b:03d}")

    def _begin_read_spans(self, lanes: list[int]) -> None:
        """Open one B span per freshly captured read on its lane track
        (closed by :meth:`_finish` with the decision args — the per-read
        lifecycle, correlated by ``read_id``)."""
        if not self._trace.enabled or not lanes:
            return
        active = self.scheduler.active
        for b in lanes:
            s = active[b]
            self._trace.begin(
                "read", pid=self._pid, tid=self._lane_tid(b), cat="read",
                args={"read_id": int(s.read.read_id), "lane": b,
                      "total_samples": int(s.read.total_samples),
                      "capture_tick": self._ticks})

    def _finish(self, b: int, decision: Decision, reason: str,
                mapped_pos: int, now: float) -> None:
        s = self.scheduler.release(b)
        total = s.read.total_samples
        if decision is Decision.EJECT:
            consumed = min(s.offset + self.policy.eject_latency_samples, total)
        else:
            # accept / exhausted: the molecule is sequenced to completion
            # (fast-forwarded here; the decision loop is done with it).
            consumed = total
        if self._source is not None:
            # the pore stays on the molecule for the signal it still has to
            # sequence after the decision — ejects hand the channel back
            # almost immediately, accepts hold it for the whole remainder
            self._source.read_done(b, self.flowcell_samples,
                                   consumed - s.offset)
        self._lane_reads[b] += 1
        rec = ReadRecord(
            channel=b, read_id=s.read.read_id, decision=decision,
            reason=reason, bases_at_decision=int(len(s.bases)),
            samples_at_decision=s.offset, samples_sequenced=consumed,
            total_samples=total, on_target=s.read.on_target,
            mapped_pos=int(mapped_pos),
            decision_ms=(now - s.started_wall) * 1e3,
            bases=s.bases)
        self.records.append(rec)
        if self._trace.enabled:
            self._trace.end(
                pid=self._pid, tid=self._lane_tid(b),
                args={"read_id": int(s.read.read_id),
                      "decision": decision.name, "reason": reason,
                      "bases": int(len(s.bases)),
                      "samples_sequenced": int(consumed),
                      "samples_saved": int(total - consumed)})
        tel = self.telemetry
        tel.completed += 1
        tel.samples += consumed
        tel.samples_saved += total - consumed
        if reason == "exhausted":
            tel.count("exhausted")
        elif reason == "timeout":
            tel.count("timeouts")
            tel.observe_latency(rec.decision_ms)
        else:
            tel.count("accepted", int(decision is Decision.ACCEPT))
            tel.count("ejected", int(decision is Decision.EJECT))
            tel.observe_latency(rec.decision_ms)

    # ------------------------------------------------------------- ticks --
    def _process_pending(self) -> None:
        p, self._pending = self._pending, None
        if p is not None:
            self._process_one(p)

    def _process_one(self, p: dict) -> None:
        """Map + decide on one dispatched tick's basecalls.

        With ``pipeline_depth=2`` this runs one tick behind the device (the
        double buffer); with depth 1 it runs inside the same tick.  Reads
        whose decision evidence is here but whose lane has already streamed
        a newer chunk simply finish with that chunk counted as consumed —
        the decision itself is identical either way.
        """
        tel = self.telemetry
        sessions = p["sessions"]
        with tel.scope(), tel.stage("basecall"), tel.stage("basecall.wait"):
            # blocks on the device step dispatched when p was created
            tokens_np = np.asarray(p["tokens"])
            lens_np = np.asarray(p["lens"])
            bases_np = np.asarray(p["bases"])
        if self._trace.enabled:
            # completion lands one tick after dispatch under depth-2
            # double-buffering: the args carry the evidence tick so the
            # dispatch -> completion lag is visible in the trace
            self._trace.instant(
                "tick.complete", pid=self._pid,
                tid=self._trace.tid(self._pid, "host"), cat="tick",
                args={"evidence_tick": p["tick"], "lanes": len(sessions)})
        active = self.scheduler.active
        for b, s in sessions.items():
            if active[b] is not s:     # lane already recycled (defensive)
                continue
            n = int(lens_np[b])
            s.append_bases(tokens_np[b, :n])
            tel.bases += n

        # map + decide on channels with a long-enough called prefix; the
        # prefix length comes from the sharded per-lane counter (bit-equal
        # to len(session.bases) — the lane pytree is the source of truth)
        map_len = self.policy.map_prefix_bases
        cand = [b for b, s in sessions.items()
                if active[b] is s
                and bases_np[b] >= self.policy.min_prefix_bases]
        if cand:
            prefixes = np.zeros((self.channels, map_len), np.int32)
            prefix_lens = np.zeros((self.channels,), np.int64)
            for b in cand:
                # latest window, not the literal prefix: a WAIT retry then
                # maps fresh bases instead of re-trying identical evidence
                window = sessions[b].bases[-map_len:]
                prefixes[b, :len(window)] = window
                prefix_lens[b] = int(bases_np[b])
            with tel.scope(), tel.stage("map"):
                res = self.mapper.map_prefixes(prefixes, stage=tel.stage)
                decisions, reasons = policy_mod.decide(
                    res.mapped, res.on_target, res.mapq, prefix_lens,
                    self.policy)
            tel.count("map.rows_wanted", len(cand))
            tel.count("map.rows_voted", int(res.voted.sum()))
            tel.count("map.upload_bytes", res.upload_bytes)
            now = time.perf_counter()
            for b in cand:
                if decisions[b] is not Decision.WAIT:
                    self._finish(b, decisions[b], reasons[b],
                                 res.positions[b], now)

        # reads that ran dry without a decision were sequenced in full —
        # judged on the offset at this evidence tick's dispatch, so a lane
        # whose *newer* in-flight chunk is the final one is not finished
        # early (its last bases are still on the device)
        now = time.perf_counter()
        for b, s in sessions.items():
            if active[b] is s and p["offsets"][b] >= s.read.total_samples:
                self._finish(b, Decision.ACCEPT, "exhausted", -1, now)

    def flush(self) -> None:
        """Resolve the in-flight double-buffered tick (if any) so telemetry
        and records cover every dispatched observation.  ``run``/``drain``
        call this; it is also safe to call at any point mid-run."""
        self._process_pending()

    def yield_mesh(self) -> None:
        """Release the device mesh to another engine between ticks.

        Waits for the dispatched-but-unconsumed tick (depth-2 double
        buffering keeps one in flight) so no dispatch of ours is pending
        on the mesh when the fleet hands it to the next tenant.  The
        logical pipeline is untouched — the synced arrays are still
        mapped/decided on our *next* tick, so decisions are bit-identical
        to an undisturbed run; we only give up the dispatch/compute
        overlap across the yield."""
        p = self._pending
        if p is not None:
            jax.block_until_ready((p["tokens"], p["lens"], p["bases"]))
            self.telemetry.count("mesh_yields_inflight")

    def detach_source(self) -> None:
        """Live flowcell detach: stop capturing new molecules, let every
        in-flight read stream to its decision.  Safe at any tick — the
        finish path stops reporting pore time to the (gone) simulator and
        ``tick()`` returns False once the occupied lanes drain."""
        if self._source is not None:
            self._source = None
            self.telemetry.count("source_detached")

    def tick(self) -> bool:
        """Advance every busy channel by one chunk; returns False when idle."""
        self.warmup()
        t0 = time.perf_counter()
        tel = self.telemetry
        # one reset scatter covers both intake paths; the fused step folds
        # the reset inside the device program instead (a fresh lane is
        # always busy this tick, so the mask always reaches the step)
        fresh = self._poll_source() + self._assign_free()
        if not self.fused:
            self._reset_lanes(fresh)
        self._begin_read_spans(fresh)
        sessions = self.scheduler.active
        busy = self.scheduler.busy
        if not busy:
            # whatever is still in flight belongs to released sessions
            # (every live session keeps its lane busy): sync and discard
            self._process_pending()
            src = self._source
            if (not self.scheduler.pending
                    and (src is None or src.exhausted)):
                return False
            # channels recovering while the source still holds molecules:
            # flowcell time advances
            self._ticks += 1
            tel.count("idle_ticks")
            tel.wall_s += time.perf_counter() - t0
            return True
        tel.steps += 1
        self._ticks += 1
        self._busy_ticks[busy] += 1

        # 1. sense: one fixed-shape chunk matrix across all channels.  A
        # read's final partial chunk is zero-filled; frames derived from the
        # fill are marked as padding so they can never emit bases.
        n_frames = self.chunk_samples // self.cfg.total_stride
        rows = np.zeros((self.channels, self.chunk_samples), np.float32)
        frame_pads = np.ones((self.channels, n_frames), np.float32)
        with tel.stage("sense"):
            for b in busy:
                s = sessions[b]
                piece = s.read.signal[s.offset: s.offset + self.chunk_samples]
                rows[b, :len(piece)] = piece
                frame_pads[b, : len(piece) // self.cfg.total_stride] = 0.0
                s.offset = min(s.offset + self.chunk_samples,
                               s.read.total_samples)

        # 2. dispatch the stateful basecall + CTC collapse for every lane.
        # jax dispatch is asynchronous: the arrays in ``pending`` are
        # futures, so the host returns from the dispatch immediately.
        with tel.scope(), tel.stage("basecall"):
            if self.fused:
                reset = np.zeros((self.channels,), np.float32)
                if fresh:
                    reset[fresh] = 1.0
                tokens, lens, self.lane_state = self._step(
                    self.params, self.lane_state, jnp.asarray(rows),
                    jnp.asarray(frame_pads), jnp.asarray(reset))
            else:
                tokens, lens, self.lane_state = self._step(
                    self.params, self.lane_state, jnp.asarray(rows),
                    jnp.asarray(frame_pads))
        tel.dispatches += 1
        if self._trace.enabled:
            # dispatch marker: processing of this tick's evidence lands in a
            # later tick.complete under depth-2 double-buffering
            self._trace.instant(
                "tick.dispatch", pid=self._pid,
                tid=self._trace.tid(self._pid, "host"), cat="tick",
                args={"tick": self._ticks, "lanes": len(busy)})
            self._trace.counter(
                "lanes", {"busy": len(busy),
                          "queue": self.scheduler.pending},
                pid=self._pid)
        tel.gauge("queue_depth", self.scheduler.pending)
        tel.gauge("lanes_busy", len(busy))
        prev = self._pending
        self._pending = {
            "tokens": tokens, "lens": lens,
            "bases": self.lane_state["bases"],
            "sessions": {b: sessions[b] for b in busy},
            "offsets": {b: sessions[b].offset for b in busy},
            "tick": self._ticks,
        }
        if self.pipeline_depth == 1:
            self._process_pending()
        elif prev is not None:
            # the double buffer: map + decide tick t-1's tokens on the host
            # while the device runs the step just dispatched for tick t
            self._process_one(prev)

        tel.wall_s += time.perf_counter() - t0
        return True

    def run(self, max_ticks: int = 100_000) -> dict:
        while self.tick():
            self.telemetry.tick_export()
            if self._ticks >= max_ticks:
                break
        # flush the in-flight tick BEFORE reading the report: the final
        # (possibly partial) tick's decisions and latency observations must
        # land in Telemetry, or report counts trail submitted reads
        self.flush()
        return self.report()

    # ----------------------------------------------------------- metrics --
    def report(self) -> dict:
        tel = self.telemetry
        if self._ticks:
            occ = self._busy_ticks / self._ticks
            tel.gauge("occupancy_mean", float(occ.mean()))
            tel.gauge("occupancy_min", float(occ.min()))
            tel.gauge("occupancy_max", float(occ.max()))
            tel.gauge("flowcell_ticks", self._ticks)
            tel.gauge("flowcell_samples", self.flowcell_samples)
        tel.gauge("pore_time_saved_samples", tel.samples_saved)
        tel.gauge("reads_per_channel_mean", float(self._lane_reads.mean()))
        out = tel.summary()
        # domain-named aliases kept alongside the unified telemetry keys
        out["reads"] = tel.completed
        out["decision_p50_ms"] = out["p50_ms"]
        out["decision_p99_ms"] = out["p99_ms"]
        for k in ("accepted", "ejected", "timeouts", "exhausted"):
            out.setdefault(k, 0)
        recs = self.records
        truth = [r for r in recs if r.on_target is not None]
        if truth:
            seq_on = sum(r.samples_sequenced for r in truth if r.on_target)
            seq_all = sum(r.samples_sequenced for r in truth)
            tot_on = sum(r.total_samples for r in truth if r.on_target)
            tot_all = sum(r.total_samples for r in truth)
            naive = tot_on / max(tot_all, 1)       # non-selective fraction
            selective = seq_on / max(seq_all, 1)   # achieved fraction
            out["on_target_frac_nonselective"] = naive
            out["on_target_frac_selective"] = selective
            out["enrichment"] = selective / max(naive, 1e-9)
            wrong_ejects = sum(r.decision is Decision.EJECT and r.on_target
                               for r in truth)
            out["on_target_eject_rate"] = wrong_ejects / max(
                sum(1 for r in truth if r.on_target), 1)
        return out
