"""Read-Until driver: the flowcell loop through ``engine.step()``.

Set-up makes the genome, the target panel and a pool of molecules from the
seed, the weights on the device, builds the engine through
``repro.engine.build("adaptive_sampling", ...)`` with the benchmark's own
pore lifecycle as its read source, and warms it up.  The window then calls
``engine.step()`` back to back (``pacing: closed``) or when each chunk is
due on the flowcell clock (``pacing: paced``).

A recorder stands in for the runtime's jitted tick and keeps each device
step's per-lane base counts; with the capture times the flowcell keeps,
they give every read its bases chunk by chunk.  After the window a seeded
sample of the reads decided in it (with the read of most bases among them)
is basecalled again by the plain reference, and each read's decision rule
is taken again, chunk by chunk, by the reference mapper on the bases the
program called.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from bench.configs import reference as ref
from bench.lib import common, signals
from bench.lib.flowcell import PoolFlowcell
from bench.lib.mapper_ref import ReferenceMapper

DECISION = {"ACCEPT": "accept", "EJECT": "eject"}


class StepRecorder:
    """Passes every call of the runtime's jitted tick through and keeps the
    per-lane base counts it returns (device arrays, read after the
    window).  It stops the run where the tick no longer returns
    ``(tokens, lens, lane_state)`` with one base count per lane."""

    def __init__(self, runtime):
        common.require(runtime, "the Read-Until runtime", "_step",
                       "channels")
        self._step = runtime._step
        self._lanes = runtime.channels
        self.lens = []
        runtime._step = self

    def __call__(self, *args):
        out = self._step(*args)
        if not isinstance(out, tuple) or len(out) != 3 or \
                tuple(out[1].shape) != (self._lanes,):
            raise RuntimeError(
                "bench: the runtime's jitted tick no longer returns "
                "(tokens, lens, lane_state) with one base count per lane")
        self.lens.append(out[1])
        return out

    def dispatched(self) -> int:
        return len(self.lens)


def setup(run, cfg: dict, traffic: dict):
    """Inputs, weights and the warmed engine; returns the run state."""
    from repro.core import basecaller as bc
    from repro.engine import build

    seed = run.seed
    clock = run.clock
    with clock.phase("synthesis"):
        # the reference panel is part of the deployment: fixed by the mix,
        # so that every seed maps against the same index
        ref_rng = np.random.default_rng(traffic["genome_seed"])
        genome = signals.random_genome(ref_rng, traffic["genome_len"])
        panel = traffic["panel"]
        intervals = signals.target_panel(ref_rng, len(genome),
                                         panel["targets"],
                                         panel["target_len"])
        mask = signals.target_mask(len(genome), intervals)
        rng = np.random.default_rng([seed, 1])
        pool = signals.molecule_pool(rng, genome, mask, traffic["pool"],
                                     traffic["read_len"], cfg["signal"])
    with clock.phase("weights"):
        params = ref.make_params(cfg, seed)
        jax.block_until_ready(params)
    with clock.phase("build"):
        bc_cfg = bc.BasecallerConfig(kernels=tuple(cfg["kernels"]),
                                     channels=tuple(cfg["channels"]),
                                     strides=tuple(cfg["strides"]),
                                     in_channels=cfg["in_channels"])
        eng = build("adaptive_sampling", params=params, cfg=bc_cfg,
                    reference=genome, targets=intervals,
                    channels=traffic["channels"], chunk=traffic["chunk"],
                    pipeline_depth=traffic["pipeline_depth"],
                    fused=traffic["fused"], mesh=traffic["mesh"], seed=seed)
        src = PoolFlowcell(pool, traffic["channels"],
                           recovery_samples=traffic["recovery_samples"],
                           start_spread_samples=traffic[
                               "start_spread_samples"],
                           rng=np.random.default_rng([seed, 2]))
        # the benchmark's pre-synthesised flowcell in place of the engine's
        common.require(eng, "the adaptive-sampling engine", "runtime",
                       "flowcell", "records", "telemetry", "flush")
        common.require(eng.runtime, "the Read-Until runtime", "_source",
                       "warmup", "mesh")
        eng.runtime._source = src
        eng.flowcell = src
    with clock.phase("warmup"):
        eng.runtime.warmup()
        recorder = StepRecorder(eng.runtime)
        src.dispatched = recorder.dispatched
        for _ in range(traffic["warm_ticks"]):
            eng.step()
        jax.effects_barrier()
    return {"eng": eng, "src": src, "pool": pool, "genome": genome,
            "mask": mask, "recorder": recorder}


def window(run, state: dict, traffic: dict) -> dict:
    """The measured window; returns what the metrics and the check read.

    ``calls`` holds one entry per ``engine.step()`` (and one for the final
    flush): when it was due, when it started and ended, and the device
    steps dispatched and records made before and after it."""
    eng = state["eng"]
    tel = eng.telemetry
    rec = state["recorder"]
    if run.trace_dir is not None:
        common.traced_stages(tel)
    chunk_s = traffic["chunk"] / traffic["sample_rate_hz"]
    paced = traffic["pacing"] == "paced"
    stage0 = dict(tel.stage_s)
    steps0 = tel.steps
    fabric0 = tel.fabric_counters()
    compiles0 = run.clock.compiles
    d0, n0 = rec.dispatched(), len(eng.records)
    calls = []
    with common.profiled(run.trace_dir):
        with common.annotate("bench.window"):
            t0 = time.perf_counter()
            k = 0
            while True:
                if paced:
                    due = t0 + k * chunk_s
                    if due - t0 >= run.seconds:
                        break
                    wait = due - time.perf_counter()
                    if wait > 0:
                        with common.annotate("bench.pace_wait"):
                            time.sleep(wait)
                else:
                    due = time.perf_counter()
                    if due - t0 >= run.seconds:
                        break
                d, n = rec.dispatched(), len(eng.records)
                start = time.perf_counter()
                with common.annotate("bench.step"):
                    eng.step()
                calls.append((due, start, time.perf_counter(), d,
                              rec.dispatched(), n, len(eng.records)))
                k += 1
            if not paced:
                d, n = rec.dispatched(), len(eng.records)
                with common.annotate("bench.flush"):
                    eng.flush()
                calls.append((None, None, time.perf_counter(), d, d, n,
                              len(eng.records)))
            t1 = time.perf_counter()
    d1, n1 = rec.dispatched(), len(eng.records)
    if paced:
        eng.flush()
    jax.effects_barrier()
    return {
        "window_s": t1 - t0,
        "calls": calls,
        "dispatch_range": (d0, d1),
        "busy_ticks": tel.steps - steps0,
        "stage_s": {n: tel.stage_s.get(n, 0.0) - stage0.get(n, 0.0)
                    for n in tel.stage_s},
        "fabric": {k: v - fabric0.get(k, 0)
                   for k, v in tel.fabric_counters().items()
                   if v - fabric0.get(k, 0)},
        "compiles_in_window": run.clock.compiles - compiles0,
        "records": (n0, n1),
    }


def finish_calls(calls: list, n_records: int) -> np.ndarray:
    """Per record made in the window, the index of the call that made it
    (-1 for records from before the window)."""
    out = np.full(n_records, -1, np.int64)
    for i, c in enumerate(calls):
        out[c[5]:c[6]] = i
    return out


def streamed_samples(src: PoolFlowcell, last: np.ndarray, d0: int, d1: int,
                     chunk: int, stride: int) -> int:
    """Read samples basecalled by the device steps ``d0 .. d1 - 1``.

    Read ``r`` streams its chunk ``j`` in step ``captured_at[r] + j`` up to
    step ``last[r]``; a chunk's samples count in whole frames, so its zero
    fill never does.  The first ``j`` chunks of a read of ``n`` samples
    hold ``min(j * chunk, n // stride * stride)`` of them."""
    c0 = np.asarray(src.captured_at, np.int64)
    totals = src.pool.lengths()[np.asarray(src.molecule_of, np.int64)]
    usable = totals // stride * stride
    lo = np.maximum(c0, d0) - c0
    hi = np.minimum(last, d1 - 1) - c0 + 1
    keep = hi > lo
    got = (np.minimum(hi * chunk, usable) - np.minimum(lo * chunk, usable))
    return int(got[keep].sum())


def decision_latencies(calls: list, recs: list, first: int,
                       depth: int) -> list:
    """Per read decided in the window (timeouts included, reads that ran
    dry not), in ms: from when the chunk that completed its evidence was
    due to when the call that decided it returned.  The evidence of a call
    is the step dispatched ``depth - 1`` calls earlier."""
    due_of = {d_before: due for due, _, _, d_before, d_after, _, _ in calls
              if d_after > d_before}
    call_of = finish_calls(calls, first + len(recs))
    lat = []
    for i, r in enumerate(recs):
        c = call_of[first + i]
        if c < 0 or r.reason == "exhausted" or calls[c][0] is None:
            continue
        _, _, end, d_before, d_after, _, _ = calls[c]
        evidence = d_before - 1 if depth == 2 else d_after - 1
        if evidence in due_of:
            lat.append((end - due_of[evidence]) * 1e3)
    return lat


def check_records(seed, recs, first, calls, lens, src, genome, mask, cfg,
                  traffic, *, operands="f32") -> dict:
    """Tokens and decisions of a seeded sample of the window's reads
    against the plain reference (with ``operands``: the reference at that
    precision in the program's place, tokens only)."""
    chunk = traffic["chunk"]
    depth = traffic["pipeline_depth"]
    stride = math.prod(cfg["strides"])
    fpc = chunk // stride
    pol = traffic["policy"]
    call_of = finish_calls(calls, first + len(recs))
    rng = np.random.default_rng([seed, 3])
    n = min(traffic["check_reads"], len(recs))
    if n == 0:
        return {"attempted": 0, "failed": 0, "checks": {}}
    pick = set(rng.choice(len(recs), size=n, replace=False).tolist())
    pick.add(int(np.argmax([len(r.bases) for r in recs])))
    sample = [recs[i] for i in sorted(pick)]
    calls_of = [call_of[first + i] for i in sorted(pick)]
    pool = src.pool
    sigs = [pool.molecule(src.molecule_of[r.read_id]) for r in sample]
    width = max(math.ceil(len(s) / chunk) for s in sigs) * chunk
    x = np.zeros((len(sigs), width), np.float32)
    for i, s in enumerate(sigs):
        x[i, :len(s)] = s
    params = ref.make_params(cfg, seed)
    with jax.default_matmul_precision("highest"):
        classes = ref.frame_classes(params, cfg, x, padding="stream")
        test = classes if operands == "f32" else ref.frame_classes(
            params, cfg, x, padding="stream", operands=operands)
    mapper = ReferenceMapper(genome, mask, traffic["align"], pol)
    got_tok, want_tok, windows, lens_at, expect = [], [], [], [], []
    bad = set()
    width = pol["map_prefix_bases"]
    for i, r in enumerate(sample):
        valid = len(sigs[i]) // stride
        toks, frames = ref.collapse(classes[i], valid)
        c = calls_of[i]
        evidence = calls[c][3] - 1 if depth == 2 else calls[c][4] - 1
        c0, ch = src.captured_at[r.read_id], src.channel_of[r.read_id]
        k = evidence - c0 + 1
        per_chunk = lens[c0:evidence + 1, ch].astype(np.int64)
        cum = np.cumsum(per_chunk)
        bases = np.asarray(r.bases)
        want_tok.append(toks[frames < k * fpc])
        if operands == "f32":
            got_tok.append(bases)
        else:
            ct, cf = ref.collapse(test[i], valid)
            got_tok.append(ct[cf < k * fpc])
        if k < 1 or cum[-1] != len(bases):
            bad.add(i)          # the steps' counts do not add up to the read
            continue
        # the decision rule again, on the program's bases after each chunk
        for j in range(1, k + 1):
            nj = int(cum[j - 1])
            last = j == k
            if nj < pol["min_prefix_bases"]:
                if last and r.reason != "exhausted":
                    bad.add(i)  # decided below the minimum prefix
                continue
            # the latest ``width`` bases, zero-filled at the tail while
            # fewer have been called
            win = np.zeros(width, np.int64)
            part = bases[max(nj - width, 0):nj]
            win[:len(part)] = part
            windows.append(win)
            lens_at.append(nj)
            if not last or r.reason == "exhausted":
                expect.append((i, ("wait", "", -1)))
            else:
                expect.append((i, (DECISION[r.decision.name], r.reason,
                                   int(r.mapped_pos))))
    decided = mapper.decide(np.stack(windows), np.asarray(lens_at)) \
        if windows else []
    for (i, want), got in zip(expect, decided):
        if got[:2] != want[:2] or (want[1] == "mapped" and got[2] != want[2]):
            bad.add(i)
    rate, n_tok, n_diff = common.token_mismatch(got_tok, want_tok)
    limits = cfg["limits"]["readuntil"]
    checks = {"token_mismatch": {"value": rate,
                                 "limit": limits["token_mismatch"]}}
    if operands == "f32":
        checks["decision_mismatch"] = {"value": len(bad),
                                       "limit": limits["decision_mismatch"]}
    return {"attempted": len(sample),
            "failed": len(bad) if operands == "f32" else n_diff,
            "checks": checks, "reference_tokens": n_tok,
            "reads_differ": n_diff, "decisions_checked": len(windows)}


def observe(run, state: dict, win: dict, traffic: dict) -> dict:
    """What the metric readers and the check take from a window."""
    cfg = run.cfg
    eng = state["eng"]
    src = state["src"]
    out = dict(win)
    mesh = eng.runtime.mesh
    out["chips_used"] = mesh.size if mesh is not None else 1
    out["lanes"] = traffic["channels"]
    out["chunk"] = traffic["chunk"]
    out["sample_rate_hz"] = traffic["sample_rate_hz"]
    first, end = win["records"]
    recs = list(eng.records)[first:end]
    calls = win["calls"]
    call_of = finish_calls(calls, end)
    d0, d1 = win["dispatch_range"]
    # the last step each read streamed in: reads still on a lane at the
    # close streamed up to the window's last step
    last = np.full(len(src.molecule_of), d1 - 1, np.int64)
    for i, r in enumerate(eng.records[:end]):
        c = call_of[i]
        last[r.read_id] = calls[c][4] - 1 if c >= 0 else d0 - 1
    out["samples"] = streamed_samples(src, last, d0, d1, traffic["chunk"],
                                      math.prod(cfg["strides"]))
    out["decision_ms"] = decision_latencies(
        calls, recs, first, traffic["pipeline_depth"]) \
        if traffic["pacing"] == "paced" else []
    out["pacer"] = pacer_summary(
        [(start - due) * 1e3 for due, start, *_ in calls if due is not None]
    ) if traffic["pacing"] == "paced" else None
    return out


def evidence(state: dict, win: dict) -> dict:
    """What the check needs of a run, free of the engine."""
    first, end = win["records"]
    return {"recs": list(state["eng"].records)[first:end], "first": first,
            "calls": win["calls"], "src": state["src"],
            "lens": np.stack([np.asarray(x)
                              for x in state["recorder"].lens]),
            "genome": state["genome"], "mask": state["mask"]}


def check(seed: int, ev: dict, cfg: dict, traffic: dict,
          operands: str = "f32") -> dict:
    return check_records(seed, ev["recs"], ev["first"], ev["calls"],
                         ev["lens"], ev["src"], ev["genome"], ev["mask"],
                         cfg, traffic, operands=operands)


def run(run, cfg: dict, traffic: dict, control: str | None = None) -> dict:
    state = setup(run, cfg, traffic)
    run.setup_done()
    win = window(run, state, traffic)
    run.after_window(win)
    out = observe(run, state, win, traffic)
    ev = evidence(state, win)
    del state
    gc.collect()
    out["check"] = check(run.seed, ev, cfg, traffic,
                         operands=control or "f32")
    if control is not None:
        out["program_check"] = check(run.seed, ev, cfg, traffic)
    return out


def pacer_summary(late_ms: list) -> dict:
    """How late the pacer called ``engine.step()`` against each chunk's due
    time: over the whole window, and its first and last tenth (a backlog
    that grows shows as a last tenth far above the first)."""
    late = np.asarray(late_ms)
    q = max(len(late) // 10, 1)
    return {"calls": len(late), "late_p50_ms": float(np.median(late)),
            "late_p95_ms": float(np.percentile(late, 95)),
            "late_max_ms": float(late.max()),
            "late_first_tenth_ms": float(late[:q].mean()),
            "late_last_tenth_ms": float(late[-q:].mean())}
