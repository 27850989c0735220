#!/usr/bin/env python3
"""Chip smoke test: drive the Read-Until main path once on a TPU.

Proves that the system starts on the chip with its Pallas kernels compiled
(not interpreted) and that they agree with the pure-jnp reference there.
It is not a benchmark: the times it prints are one cold run of each phase.

  python chip_smoke.py            phases (a) (b) (c) on one device
  python chip_smoke.py --chips 4  phase (b)'s loop on a 4-device lane mesh
                                  against the same loop on one device

(a) the ``flowcell_512`` preset as users run it (512 channels, chunk 256,
    exact step decoder, fused step, double-buffered), drained to the end;
(b) the paper's 460,261-parameter CNN through the same 512-channel loop for
    a fixed number of ticks; every tick's inputs are replayed through the
    reference step, and the first tick's logits are compared as well;
(c) offline basecalling (``basecall`` preset ``default``) against the
    reference target.

The last line of standard output is one JSON object naming the device.  A
run with no TPU exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.monitoring  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import basecaller as bc  # noqa: E402
from repro.data import nanopore  # noqa: E402
from repro.engine import build  # noqa: E402
from repro.kernels import fabric  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.realtime import runtime as rt  # noqa: E402

CHANNELS = 512
PAPER_TICKS = 24
BASECALL_ROWS = 128            # 8 batches of the basecall preset's 16

# Tolerances of the kernel-vs-reference comparison on the chip.  Neither
# side is exact: at the default precision a TPU multiplies f32 operands on
# the MXU with bf16 rounding (unit roundoff 2^-9 per operand), so each of
# the six layers can move its output by ~2^-8 of its scale, and the two
# targets round differently.  Six layers give ~6 * 2^-8 = 0.023 of the
# logit scale; the bound below allows twice that.  A frame whose top two
# classes are closer than that error may flip its argmax, which costs at
# most one edit per flip in the collapsed tokens.
MAX_LOGIT_ERR_FRAC = 0.05      # max |logit - ref| / max |ref|
MIN_TOKEN_AGREEMENT = 0.95     # 1 - edit distance / reference tokens


# ------------------------------------------------------------- helpers ----
class CacheEvents:
    """Counts JAX's persistent compilation cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def counters_since(base: dict) -> dict:
    jax.effects_barrier()          # flush the execution-time count callbacks
    return fabric.counters_delta(base)


def check_counters(phase: str, delta: dict, *, fused: bool) -> None:
    """Fail on interpreted kernels, on a fused-step fallback and on a
    mapper that did not run its compiled kernel."""
    interp = {k: v for k, v in delta.items()
              if k.startswith("fabric.dispatch.") and
              k.endswith(".pallas_interpret")}
    assert not interp, f"{phase}: interpreted kernels on the chip: {interp}"
    if fused:
        fb = {k: v for k, v in delta.items()
              if k.startswith("fabric.fallback.fused_stream.")}
        assert not fb, f"{phase}: fused step fell back: {fb}"
        assert delta.get("fabric.dispatch.fused_stream.pallas_tpu", 0) > 0, \
            f"{phase}: fused step never ran its kernel"
        assert delta.get("fabric.dispatch.banded_align.pallas_tpu", 0) > 0, \
            f"{phase}: mapper never ran its kernel"


def print_counters(phase: str, delta: dict) -> None:
    for k in sorted(delta):
        if k.startswith(("fabric.dispatch.", "fabric.fallback.")):
            print(f"  [{phase}] {k} = {delta[k]}")


def edit_distances(a, la, b, lb) -> np.ndarray:
    """Row-wise Levenshtein distance between ``a[r, :la[r]]`` and
    ``b[r, :lb[r]]``, vectorised over rows (plain numpy)."""
    r, n = a.shape
    m = b.shape[1]
    out = np.zeros(r, np.int64)
    prev = np.tile(np.arange(m + 1), (r, 1))
    out[la == 0] = lb[la == 0]
    for i in range(1, n + 1):
        cur = np.empty_like(prev)
        cur[:, 0] = i
        for j in range(1, m + 1):
            sub = prev[:, j - 1] + (a[:, i - 1] != b[:, j - 1])
            cur[:, j] = np.minimum(np.minimum(prev[:, j], cur[:, j - 1]) + 1,
                                   sub)
        done = la == i
        out[done] = cur[done, lb[done]]
        prev = cur
    return out


def token_agreement(tok, lens, ref_tok, ref_lens) -> tuple[float, int]:
    """``1 - sum(edit distance) / sum(reference tokens)`` over rows, and
    the number of rows that differ at all."""
    tok, ref_tok = np.asarray(tok), np.asarray(ref_tok)
    lens, ref_lens = np.asarray(lens), np.asarray(ref_lens)
    same = (lens == ref_lens) & np.all(
        (tok == ref_tok) | (np.arange(tok.shape[1]) >= lens[:, None]), axis=1)
    diff = ~same
    ed = edit_distances(tok[diff], lens[diff], ref_tok[diff], ref_lens[diff])
    return 1.0 - ed.sum() / max(int(ref_lens.sum()), 1), int(diff.sum())


def logit_error(got, want) -> tuple[float, float]:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = float(np.max(np.abs(got - want)))
    return err, err / max(float(np.max(np.abs(want))), 1e-30)


class StepRecorder:
    """Stands in for a runtime's jitted tick and keeps every tick's inputs
    and tokens, so the same stream can be replayed through another target
    (install after ``warmup()``, so the warm-up tick is not kept)."""

    def __init__(self, runtime):
        self._step = runtime._step
        self.ticks = []
        runtime._step = self

    def __call__(self, params, lane, *inputs):
        out = self._step(params, lane, *inputs)
        self.ticks.append((inputs, out[0], out[1]))
        return out


# -------------------------------------------------------------- phases ----
def phase_flowcell(seed: int, *, channels: int = CHANNELS, **overrides):
    """(a) the flowcell_512 preset, drained to completion."""
    base = fabric.counters()
    eng = build("adaptive_sampling", preset="flowcell_512", mesh=None,
                seed=seed, channels=channels, **overrides)
    n_reads = eng.flowcell.config.n_reads
    t0 = time.perf_counter()
    eng.runtime.warmup()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    report = eng.drain()
    jax.effects_barrier()
    steady_s = time.perf_counter() - t0
    delta = counters_since(base)
    bases = eng.telemetry.bases
    print(f"(a) flowcell_512: {channels} channels, {n_reads} molecules, "
          f"{report['flowcell_ticks']:.0f} ticks; compile+warmup "
          f"{compile_s:.3f} s, steady {steady_s:.3f} s, {bases} bases, "
          f"{bases / steady_s:.1f} bases/s; reads {report['reads']}, "
          f"enrichment {report['enrichment']:.3f}, signal saved "
          f"{report['signal_saved_frac']:.3f}")
    print_counters("a", delta)
    assert report["reads"] == n_reads, "not every molecule resolved"
    assert report["enrichment"] > 1.0, "no enrichment achieved"
    check_counters("a", delta, fused=True)


def paper_loop(seed: int, *, channels: int, ticks: int, mesh, **overrides):
    """The paper CNN through the 512-channel flowcell loop for ``ticks``
    ticks; returns the engine, its step recorder and the phase times."""
    cfg = bc.BasecallerConfig()
    params = bc.init(jax.random.key(seed), cfg)
    assert bc.num_params(params) == 460_261
    eng = build("adaptive_sampling", preset="flowcell_512", mesh=mesh,
                params=params, cfg=cfg, seed=seed, channels=channels,
                flowcell={"n_reads": 2 * channels, "seed": seed},
                **overrides)
    t0 = time.perf_counter()
    eng.runtime.warmup()
    compile_s = time.perf_counter() - t0
    rec = StepRecorder(eng.runtime)
    t0 = time.perf_counter()
    eng.runtime.run(max_ticks=ticks)
    jax.effects_barrier()
    steady_s = time.perf_counter() - t0
    return eng, rec, compile_s, steady_s


def phase_paper(seed: int, *, channels: int = CHANNELS,
                ticks: int = PAPER_TICKS, **overrides):
    """(b) the paper CNN on the chip's kernels against the reference."""
    base = fabric.counters()
    eng, rec, compile_s, steady_s = paper_loop(
        seed, channels=channels, ticks=ticks, mesh=None, **overrides)
    delta = counters_since(base)
    bases = eng.telemetry.bases
    print(f"(b) paper CNN loop: {channels} channels, {len(rec.ticks)} ticks; "
          f"compile+warmup {compile_s:.3f} s, steady {steady_s:.3f} s, "
          f"{bases} bases, {bases / steady_s:.1f} bases/s")
    print_counters("b", delta)
    check_counters("b", delta, fused=True)

    # the recorded per-tick inputs through the reference step, at the
    # chip's default matmul precision and at exact f32 (diagnostic only)
    cfg, params = eng.runtime.cfg, eng.runtime.params
    kernel = [(tokens, lens) for _, tokens, lens in rec.ticks]
    ref = replay(rec, cfg, params, channels)
    with jax.default_matmul_precision("highest"):
        exact = replay(rec, cfg, params, channels)
    agreement, n_tok, n_diff = stream_agreement(kernel, ref)

    # first tick's logits: the unfused conv1d/matmul kernels vs reference
    rows = rec.ticks[0][0][0]
    state = bc.init_stream_state(cfg, channels)
    base = fabric.counters()
    got, _ = bc.apply_stream(params, state, rows, cfg, fabric=eng.fabric)
    delta = counters_since(base)
    print_counters("b logits", delta)
    check_counters("b logits", delta, fused=False)
    want, _ = bc.apply_stream(params, state, rows, cfg, fabric="reference")
    with jax.default_matmul_precision("highest"):
        f32, _ = bc.apply_stream(params, state, rows, cfg,
                                 fabric="reference")
    err, frac = logit_error(got, want)
    print(f"(b) vs reference: max |logit err| {err:.6g} "
          f"({frac:.6g} of max |logit| {float(jnp.max(jnp.abs(want))):.6g}, "
          f"tolerance {MAX_LOGIT_ERR_FRAC}); fused token agreement "
          f"{agreement:.6f} over {n_tok} tokens, {n_diff} lane-ticks differ "
          f"(tolerance {MIN_TOKEN_AGREEMENT})")
    print(f"(b) vs exact f32 (not asserted): max |logit err| kernels "
          f"{logit_error(got, f32)[0]:.6g}, reference "
          f"{logit_error(want, f32)[0]:.6g}; token agreement fused "
          f"{stream_agreement(kernel, exact)[0]:.6f}, reference "
          f"{stream_agreement(ref, exact)[0]:.6f}")
    assert frac <= MAX_LOGIT_ERR_FRAC, "paper CNN logits off the reference"
    assert agreement >= MIN_TOKEN_AGREEMENT, "fused tokens off the reference"
    return {"logit_err": err, "agreement": agreement}


def replay(rec: StepRecorder, cfg, params, channels: int) -> list:
    """Every recorded tick's inputs through the reference fused step, with
    its own lane state; returns ``[(tokens, lens), ...]``."""
    step = rt.build_step_fn(cfg, fabric.FabricPolicy("reference"),
                            fused=True)
    lane = rt.init_lane_state(cfg, channels)
    out = []
    for inputs, _, _ in rec.ticks:
        tokens, lens, lane = step(params, lane, *inputs)
        out.append((tokens, lens))
    return out


def stream_agreement(ticks, ref_ticks) -> tuple[float, int, int]:
    """Mean per-tick token agreement, reference tokens, differing
    lane-ticks."""
    agree, n_tok, n_diff = [], 0, 0
    for (tokens, lens), (ref_tokens, ref_lens) in zip(ticks, ref_ticks):
        a, d = token_agreement(tokens, lens, ref_tokens, ref_lens)
        agree.append(a)
        n_tok += int(np.asarray(ref_lens).sum())
        n_diff += d
    return float(np.mean(agree)), n_tok, n_diff


def basecall_rows(seed: int, n: int, chunk: int) -> np.ndarray:
    """``n`` normalised pore-model signal rows of ``chunk`` samples."""
    rng = np.random.default_rng(seed)
    pm = nanopore.PoreModel()
    rows = np.zeros((n, chunk), np.float32)
    for i in range(n):
        seq = rng.integers(1, 5, size=chunk // 4).astype(np.int32)
        sig, _ = nanopore.simulate_read(rng, seq, pm)
        sig = nanopore.normalize(sig)[:chunk]
        rows[i, :len(sig)] = sig
    return rows


def phase_basecall(seed: int, *, rows: int = BASECALL_ROWS, **overrides):
    """(c) the offline basecall engine against the reference target."""
    base = fabric.counters()
    eng = build("basecall", preset="default", seed=seed, **overrides)
    sig = basecall_rows(seed, rows, eng.chunk)
    t0 = time.perf_counter()
    first = eng.serve(sig[:eng.batch])
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rest = eng.serve(sig[eng.batch:])
    steady_s = time.perf_counter() - t0
    delta = counters_since(base)
    reads = first + rest
    bases = sum(len(r) for r in rest)
    print(f"(c) basecall: {rows} rows x {eng.chunk} samples, batch "
          f"{eng.batch}; compile+first batch {compile_s:.3f} s, steady "
          f"{steady_s:.3f} s, {bases} bases, {bases / steady_s:.1f} bases/s")
    print_counters("c", delta)
    check_counters("c", delta, fused=False)

    ref = build("basecall", preset="default", seed=seed,
                **{**overrides, "fabric": "reference"})
    ref_reads = ref.serve(sig)
    width = max(max(len(r) for r in reads), max(len(r) for r in ref_reads))

    def pack(rs):
        out = np.zeros((len(rs), width), np.int32)
        for i, r in enumerate(rs):
            out[i, :len(r)] = r
        return out, np.asarray([len(r) for r in rs])

    agreement, n_diff = token_agreement(*pack(reads), *pack(ref_reads))
    x = jnp.asarray(sig[:eng.batch])
    got = bc.apply(eng.params, x, eng.cfg, fabric=eng.fabric)
    want = bc.apply(eng.params, x, eng.cfg, fabric="reference")
    err, frac = logit_error(got, want)
    print(f"(c) vs reference: max |logit err| {err:.6g} ({frac:.6g} of max "
          f"|logit|, tolerance {MAX_LOGIT_ERR_FRAC}); token agreement "
          f"{agreement:.6f}, {n_diff} of {rows} reads differ "
          f"(tolerance {MIN_TOKEN_AGREEMENT})")
    assert frac <= MAX_LOGIT_ERR_FRAC, "basecall logits off the reference"
    assert agreement >= MIN_TOKEN_AGREEMENT, "basecall tokens off reference"
    return {"logit_err": err, "agreement": agreement}


def phase_mesh(seed: int, n_devices: int, *, channels: int = CHANNELS,
               ticks: int = PAPER_TICKS, **overrides):
    """Phase (b)'s loop on an ``n_devices`` lane mesh and on one device:
    identical tokens and decisions, lane state spread over every device."""
    runs = {}
    for mesh in (n_devices, None):
        eng, rec, compile_s, steady_s = paper_loop(
            seed, channels=channels, ticks=ticks, mesh=mesh, **overrides)
        runs[mesh] = (eng, rec)
        print(f"(mesh) mesh={mesh}: {len(rec.ticks)} ticks, compile+warmup "
              f"{compile_s:.3f} s, steady {steady_s:.3f} s, "
              f"{eng.telemetry.bases} bases, {len(eng.records)} decisions")
    (eng_m, rec_m), (eng_1, rec_1) = runs[n_devices], runs[None]
    assert len(rec_m.ticks) == len(rec_1.ticks)
    for t, (a, b) in enumerate(zip(rec_m.ticks, rec_1.ticks)):
        assert np.array_equal(np.asarray(a[1]), np.asarray(b[1])) and \
            np.array_equal(np.asarray(a[2]), np.asarray(b[2])), \
            f"tokens differ at tick {t}"

    def decisions(eng):
        return [(r.read_id, r.channel, r.decision.name, r.reason,
                 r.bases_at_decision, r.samples_sequenced)
                for r in eng.records]

    assert decisions(eng_m) == decisions(eng_1), "decisions differ"
    per_shard = channels // n_devices
    for leaf in jax.tree.leaves(eng_m.runtime.lane_state):
        shards = leaf.addressable_shards
        devices = {s.device for s in shards}
        assert len(devices) == n_devices, (leaf.shape, devices)
        # an empty leaf (the head layer carries no rows) holds no lanes
        assert leaf.size == 0 or all(
            s.data.shape[0] == per_shard for s in shards), leaf.shape
    print(f"(mesh) tokens of {len(rec_m.ticks)} ticks and "
          f"{len(eng_m.records)} decisions identical; every lane-state leaf "
          f"on {n_devices} distinct devices, {per_shard} lanes each")


# ---------------------------------------------------------------- main ----
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the lane-mesh phase, on 4 chips")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform}); "
                 "this test runs on the chip only")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {len(devices)}")
    cache_dir = enable_compile_cache()
    cache = CacheEvents()
    print(f"chip_smoke (not a benchmark): {dev.device_kind} x "
          f"{len(devices)}, jax {jax.__version__}, compile cache "
          f"{cache_dir}")

    t0 = time.perf_counter()
    if args.chips > 1:
        phase_mesh(args.seed, args.chips)
    else:
        phase_flowcell(args.seed)
        phase_paper(args.seed)
        phase_basecall(args.seed)
    print(f"total {time.perf_counter() - t0:.3f} s; persistent compile "
          f"cache: {cache.hits} hits, {cache.misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
