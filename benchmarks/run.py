"""Benchmark harness — one function per paper table/claim.

Prints ``name,us_per_call,derived`` CSV rows.  Wall times are CPU-host
numbers (this container); the ``derived`` column carries the paper-anchored
quantity (bases/s, speedup, Mb/s, roofline fraction) each claim is about.

  bench_basecaller       Sec III: CNN basecaller throughput + MAT 15x/13x
  bench_edit_distance    Sec III: ED engine, 100x100 comparisons, 40x/900Kb/s
  bench_alignment        Sec II-B.2: seed-and-extend reads/s
  bench_variant_caller   Sec II-B.3: pileup-CNN sites/s
  bench_pipeline         Sec II-B.1: ingest 30 Mb/s, >100x audio
  bench_ctc              basecaller decode path tokens/s
  bench_moe_dispatch     §Perf: scatter vs one-hot-einsum dispatch FLOPs
  bench_roofline         per-cell dominant roofline term (from dry-run JSON)
  bench_adaptive         Read-Until loop: decision latency + signal saved
                         (see adaptive_sampling.py; stateful streaming vs
                         re-running the CNN over the growing read)
  bench_kernel_dispatch  compute fabric: per-op throughput on each execution
                         target + dispatch/fallback counter deltas
  bench_quant            repro.quant: read accuracy + throughput + modeled
                         SoC energy per precision (fp32 / bf16 / int8) on a
                         fixed-seed micro basecaller — the CI quant-parity
                         artifact and analysis/report.py --section quant
  bench_flowcell         flowcell-scale Read-Until: aggregate bases/s vs
                         channel count (and vs lane-mesh size when multiple
                         devices exist) on the deterministic step encoder —
                         the CI flowcell-smoke artifact (BENCH_flowcell.json).
                         Ends with the obs-overhead pair (traced vs untraced
                         bases/s, acceptance: within 5%) and exports the
                         traced run's trace_flowcell.json (Chrome trace,
                         Perfetto-loadable) + timeseries_flowcell.jsonl
  bench_fleet            repro.fleet: bursty 2-tenant fleet (basecall +
                         lm_decode, one mesh) vs each tenant solo on the
                         same arrival schedule — aggregate reqs/s must be
                         >= 1.5x the worse solo (idle-slot filling), the
                         CI fleet-smoke artifact (BENCH_fleet.json +
                         trace_fleet.json)
  bench_model_shard      repro.distributed.tp: replicated vs (data=1,
                         model=2) lm_decode — tokens/s, per-device param
                         bytes, int8 bitwise parity, pre-partitioned
                         checkpoint-load counters — the CI
                         model-shard-smoke artifact (BENCH_models.json)
  bench_field            repro.field: N edge sequencers uplinking
                         compressed read frames through a lossy channel to
                         one aggregator — outbreak-detection latency,
                         bytes-on-wire vs raw signal (bar: >= 20x vs the
                         sequenced-signal baseline), exact read
                         conservation under reorder/dup — the CI
                         field-smoke artifact (BENCH_field.json +
                         trace_field.json)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

ROWS: list[tuple[str, float, str]] = []


def row(name: str, us: float, derived: str):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6, out


def bench_basecaller():
    from repro.core import basecaller as bc
    from repro.core.soc_model import SoCModel
    cfg = bc.BasecallerConfig()
    params = bc.init(jax.random.key(0), cfg)
    sig = jax.random.normal(jax.random.key(1), (8, 4096), jnp.float32)
    fn = jax.jit(lambda p, s: bc.apply(p, s, cfg))
    us, logits = timeit(fn, params, sig)
    samples = sig.size
    bases = samples / 9.0
    m = SoCModel()
    row("basecaller_fwd", us, f"host_bases_per_s={bases / (us / 1e6):.0f}")
    row("basecaller_params", 0.0, f"count={bc.num_params(params)}"
        f";two_layer_frac={bc.weight_concentration(params):.3f}")
    row("soc_mat_speedup", 0.0,
        f"modeled={m.mat_speedup():.1f}x;paper=15x")
    row("soc_mat_energy", 0.0,
        f"modeled={m.mat_energy_efficiency():.1f}x;paper=13x")
    row("soc_basecall_rate", 0.0,
        f"modeled_bases_per_s={m.basecall_bases_per_s():.0f}"
        f";realtime_sensors={m.sensors_served():.1f}")
    row("tpu_sensors_per_chip", 0.0,
        f"modeled={m.tpu_sensors_per_chip():.0f}@40%MFU")


def bench_edit_distance():
    from repro.core.soc_model import SoCModel
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    p, m, n = 128, 100, 100
    q = jnp.asarray(rng.integers(1, 5, (p, m)).astype(np.int32))
    t = jnp.asarray(rng.integers(1, 5, (p, n)).astype(np.int32))
    fn = jax.jit(lambda a, b: ops.edit_distance(a, b, fabric="reference"))
    us, _ = timeit(fn, q, t)
    pairs_per_s = p / (us / 1e6)
    soc = SoCModel()
    row("ed_100x100_batch128", us,
        f"host_pairs_per_s={pairs_per_s:.0f}"
        f";host_kbase_per_s={pairs_per_s * m / 1e3:.0f}")
    row("soc_ed_speedup", 0.0, f"modeled={soc.ed_speedup():.1f}x;paper=40x")
    row("soc_ed_rate", 0.0,
        f"modeled_kbase_per_s={soc.ed_kbase_per_s():.0f};paper~900")
    # wavefront kernel (interpret mode): correctness-path cell rate
    us_k, _ = timeit(
        lambda a, b: ops.edit_distance(a[:8], b[:8], block_p=8,
                                       fabric="pallas_interpret"),
        q, t, n=1, warmup=1)
    row("ed_kernel_interpret_8", us_k,
        f"cells_per_s={8 * m * n / (us_k / 1e6):.0f}(interpret)")


def bench_alignment():
    from repro.core import fm_index, seed_extend
    from repro.data import genome as G
    rng = np.random.default_rng(1)
    genome = G.random_genome(rng, 30_000)
    t0 = time.perf_counter()
    index = fm_index.FMIndex.build(genome)
    build_us = (time.perf_counter() - t0) * 1e6
    reads, _ = G.sample_reads(rng, genome, n_reads=64, read_len=150,
                              error_rate=0.05)
    t0 = time.perf_counter()
    res = seed_extend.align_reads(index, genome, reads)
    align_us = (time.perf_counter() - t0) * 1e6
    row("fm_index_build_30kb", build_us, f"bases={len(genome)}")
    row("align_64reads_150bp", align_us,
        f"reads_per_s={64 / (align_us / 1e6):.0f}"
        f";accept_rate={res.accepted.mean():.2f}")


def bench_variant_caller():
    from repro.core import variant_caller as vc
    cfg = vc.CallerConfig()
    params = vc.init(jax.random.key(0), cfg)
    wins = jax.random.normal(jax.random.key(1), (256, cfg.window,
                                                 vc.N_FEATURES))
    fn = jax.jit(lambda p, w: vc.apply(p, w, cfg))
    us, _ = timeit(fn, params, wins)
    row("variant_caller_256sites", us,
        f"sites_per_s={256 / (us / 1e6):.0f}")


def bench_pipeline():
    import repro.engine as engine_api
    from repro.core import basecaller as bc
    from repro.data.nanopore import PoreModel, raw_bitrate_bps
    cfg = bc.BasecallerConfig()
    params = bc.init(jax.random.key(0), cfg)
    eng = engine_api.build("pathogen_pipeline", params=params, cfg=cfg)
    rng = np.random.default_rng(2)
    chunks = [rng.normal(size=(32, 2048)).astype(np.float32)
              for _ in range(4)]
    t0 = time.perf_counter()
    for chunk in chunks:
        eng.submit(chunk)
    eng.drain()
    us = (time.perf_counter() - t0) * 1e6
    ingest = raw_bitrate_bps(PoreModel(), channels=512)
    row("stream_pipeline_4x32x2048", us,
        f"samples_per_s={eng.telemetry.samples / (us / 1e6):.0f}")
    row("sensor_ingest", 0.0,
        f"Mbps={ingest / 1e6:.1f};vs_audio={ingest / 256e3:.0f}x;paper>100x")


def bench_ctc():
    from repro.core import ctc
    logits = jax.random.normal(jax.random.key(0), (32, 512, 5))
    paddings = jnp.zeros((32, 512))
    labels = jax.random.randint(jax.random.key(1), (32, 64), 1, 5)
    lpad = jnp.zeros((32, 64))
    fn = jax.jit(ctc.ctc_loss)
    us, _ = timeit(fn, logits, paddings, labels, lpad)
    row("ctc_loss_32x512", us,
        f"frames_per_s={32 * 512 / (us / 1e6):.0f}")
    us, _ = timeit(jax.jit(ctc.greedy_decode), logits)
    row("ctc_greedy_32x512", us,
        f"frames_per_s={32 * 512 / (us / 1e6):.0f}")


def bench_moe_dispatch():
    """FLOP structure: scatter dispatch vs the quadratic one-hot einsum."""
    t, e, k, d, cap = 4096, 16, 2, 256, 640
    einsum_flops = 2 * t * e * cap * d * 2      # send + receive
    expert_flops = 2 * t * k * 3 * d * (4 * d)  # the useful work (ff=4d)
    row("moe_dispatch_einsum", 0.0,
        f"dispatch_flops={einsum_flops:.2e}"
        f";expert_flops={expert_flops:.2e}"
        f";overhead={einsum_flops / expert_flops:.2f}x")
    row("moe_dispatch_scatter", 0.0,
        "dispatch_flops=0;data_movement_only (see EXPERIMENTS.md §Perf)")


def bench_roofline():
    base = os.path.join(os.path.dirname(__file__), "..")
    path = os.path.join(base, "dryrun_report_opt.json")  # optimized table
    if not os.path.exists(path):
        path = os.path.join(base, "dryrun_report.json")
    if not os.path.exists(path):
        row("roofline", 0.0, "dryrun_report.json missing (run dryrun first)")
        return
    with open(path) as f:
        cells = json.load(f)
    for r in cells:
        if r.get("status") != "ok":
            continue
        rl = r["roofline"]
        dom_s = max(rl["compute_s"], rl["memory_s"], rl["collective_s"])
        frac = rl["compute_s"] / dom_s if dom_s > 0 else 0.0
        row(f"roofline:{r['arch']}:{r['shape']}", dom_s * 1e6,
            f"dominant={rl['dominant']};roofline_frac={frac:.3f}"
            f";useful_flops={rl['useful_flops_ratio']:.3f}")


def bench_adaptive():
    import adaptive_sampling as ad
    ad.bench_stream_state()
    ad.bench_adaptive()


def bench_flowcell(smoke: bool = False):
    import flowcell as fcb
    fcb.bench_flowcell(row, smoke=smoke)


def bench_fleet(smoke: bool = False):
    import fleet as flb
    flb.bench_fleet(row, smoke=smoke)


def bench_field(smoke: bool = False):
    import field as fdb
    fdb.bench_field(row, smoke=smoke)


def bench_model_shard(smoke: bool = False):
    import model_shard as msb
    msb.bench_model_shard(row, smoke=smoke)


def bench_kernel_dispatch():
    """Compute fabric: each registered op on each target, with the
    dispatch/fallback counters the engine telemetry surfaces."""
    from repro.kernels import fabric, ops
    rng = np.random.default_rng(0)
    key = jax.random.key

    # inputs built once, outside the timed region (like every other bench)
    mm_a = jax.random.normal(key(0), (256, 256), jnp.float32)
    mm_b = jax.random.normal(key(1), (256, 256), jnp.float32)
    cv_x = jax.random.normal(key(0), (4, 512, 64), jnp.float32)
    cv_w = jax.random.normal(key(1), (5, 64, 128), jnp.float32)
    ed_q = jnp.asarray(rng.integers(1, 5, (32, 64)).astype(np.int32))
    ed_t = jnp.asarray(rng.integers(1, 5, (32, 64)).astype(np.int32))
    fa_q = jax.random.normal(key(0), (1, 4, 256, 64), jnp.float32)
    fa_k = jax.random.normal(key(1), (1, 4, 256, 64), jnp.float32)
    fa_v = jax.random.normal(key(2), (1, 4, 256, 64), jnp.float32)
    sx = jax.random.normal(key(0), (4, 256, 16)) * 0.5
    sla = -jax.nn.softplus(jax.random.normal(key(1), (4, 256)))
    sb = jax.random.normal(key(2), (4, 256, 32)) * 0.3
    sc = jax.random.normal(key(3), (4, 256, 32)) * 0.3
    jax.block_until_ready((mm_a, mm_b, cv_x, cv_w, ed_q, ed_t, fa_q, fa_k,
                           fa_v, sx, sla, sb, sc))

    cases = {
        "matmul": lambda fab: ops.mat_mul(mm_a, mm_b, fabric=fab),
        "conv1d": lambda fab: ops.conv1d(cv_x, cv_w, padding="valid",
                                         fabric=fab),
        "edit_distance": lambda fab: ops.edit_distance(ed_q, ed_t,
                                                       fabric=fab),
        "banded_align": lambda fab: ops.banded_align(ed_q, ed_t, band=16,
                                                     local=True, fabric=fab),
        "flash_attention": lambda fab: ops.flash_attention(fa_q, fa_k, fa_v,
                                                           fabric=fab),
        "ssd_scan": lambda fab: ops.ssd_scan(sx, sla, sb, sc, fabric=fab),
    }
    targets = ["reference", "pallas_interpret"]
    if jax.default_backend() == "tpu":
        targets.append("pallas_tpu")
    for op, thunk in cases.items():
        for target in targets:
            n = 3 if target == "reference" else 1
            jax.block_until_ready(thunk(target))  # warmup/compile
            # snapshot AFTER warmup so dispatch counts match the timed calls
            base = fabric.counters()
            us, _ = timeit(lambda: thunk(target), n=n, warmup=0)
            delta = fabric.counters_delta(base)
            dispatched = delta.get(f"fabric.dispatch.{op}.{target}", 0)
            fallbacks = sum(v for k, v in delta.items()
                            if k.startswith(f"fabric.fallback.{op}."))
            row(f"kernel_dispatch:{op}:{target}", us,
                f"dispatches={dispatched};fallbacks={fallbacks}"
                f";calls_per_s={1e6 / max(us, 1e-9):.1f}")


def bench_quant():
    """Accuracy vs energy across precisions: calibrate once, quantize once,
    compare read accuracy / host throughput / modeled SoC MAC energy of
    fp32 vs bf16 vs stored-int8 on fixed seeds."""
    import dataclasses

    from repro import quant
    from repro.core import basecaller as bc
    from repro.core import ctc
    from repro.core.soc_model import SoCModel
    from repro.data import nanopore
    from repro.kernels import ref
    from repro.train.micro_basecaller import DEMO_PORE, train_micro_basecaller
    from repro.utils.tree import tree_cast

    cfg, params = train_micro_basecaller(steps=300, seed=0)
    rng = np.random.default_rng(123)
    eval_batch = nanopore.make_ctc_batch(rng, batch=32, seq_len=40,
                                         pm=DEMO_PORE)
    signal = jnp.asarray(eval_batch["signal"])
    spad = jnp.asarray(eval_batch["signal_paddings"])
    labels = jnp.asarray(eval_batch["labels"])
    label_lens = jnp.asarray(
        (1.0 - eval_batch["label_paddings"]).sum(axis=1).astype(np.int32))
    # calibration stream: held-out simulated chunks (never the eval reads)
    calib = [nanopore.make_ctc_batch(rng, batch=4, seq_len=40,
                                     pm=DEMO_PORE)["signal"]
             for _ in range(4)]

    def read_accuracy(pv, cfgv):
        logits = bc.apply(pv, signal, cfgv)
        lp = spad[:, :: cfgv.total_stride][:, : logits.shape[1]]
        tokens, lens = ctc.greedy_decode(logits, lp)
        dists = ref.edit_distance(tokens, labels, q_len=lens,
                                  t_len=label_lens)
        per_read = 1.0 - np.asarray(dists) / np.maximum(
            np.asarray(label_lens), 1)
        return float(per_read.mean())

    variants = {
        "fp32": (params, cfg),
        "bf16": (tree_cast(params, jnp.bfloat16),
                 dataclasses.replace(cfg, dtype=jnp.bfloat16)),
        "int8": (bc.quantize(params, cfg, chunks=calib,
                             observer="percentile", pct=99.9), cfg),
    }
    soc = SoCModel(bc_cfg=cfg, samples_per_base=DEMO_PORE.mean_dwell)
    samples = int(signal.size)
    bases = samples / DEMO_PORE.mean_dwell
    acc_fp32 = None
    for name, (pv, cfgv) in variants.items():
        us, _ = timeit(lambda: bc.apply(pv, signal, cfgv), n=3, warmup=1)
        acc = read_accuracy(pv, cfgv)
        if acc_fp32 is None:
            acc_fp32 = acc
        precision = quant.params_precision(pv)
        energy_j = soc.basecall_energy_j(samples, precision)
        row(f"quant:{name}", us,
            f"read_acc={acc:.4f};acc_delta_vs_fp32={acc - acc_fp32:+.4f}"
            f";host_bases_per_s={bases / (us / 1e6):.0f}"
            f";soc_pj_per_base={energy_j / bases * 1e12:.1f}"
            f";energy_ratio_vs_fp32="
            f"{soc.mac_energy_j('fp32') / soc.mac_energy_j(precision):.1f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fast subset (skips the adaptive-sampling bench, "
                         "which trains a micro-basecaller)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as JSON (e.g. BENCH_smoke.json) "
                         "for perf-trajectory tracking")
    ap.add_argument("--only", metavar="NAMES", default=None,
                    help="comma-separated bench names to run (e.g. "
                         "'kernel_dispatch' for the CI kernel artifact)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    benches = {
        "basecaller": bench_basecaller,
        "edit_distance": bench_edit_distance,
        "alignment": bench_alignment,
        "variant_caller": bench_variant_caller,
        "pipeline": bench_pipeline,
        "ctc": bench_ctc,
        "moe_dispatch": bench_moe_dispatch,
        "roofline": bench_roofline,
        "kernel_dispatch": bench_kernel_dispatch,
        "adaptive": bench_adaptive,
        "quant": bench_quant,
        "flowcell": lambda: bench_flowcell(smoke=args.smoke),
        "fleet": lambda: bench_fleet(smoke=args.smoke),
        "field": lambda: bench_field(smoke=args.smoke),
        "model_shard": lambda: bench_model_shard(smoke=args.smoke),
    }
    if args.only:
        selected = [n.strip() for n in args.only.split(",")]
        unknown = [n for n in selected if n not in benches]
        if unknown:
            ap.error(f"unknown benches {unknown}; available: "
                     f"{sorted(benches)}")
    else:
        # adaptive and quant train a micro basecaller, flowcell sweeps up to
        # 512 channels, fleet sleeps through bursty arrival schedules, field
        # compiles one engine per edge device, model_shard needs a 2-device
        # mesh — all skipped in smoke (run via --only)
        selected = [n for n in benches
                    if n not in ("adaptive", "quant", "flowcell", "fleet",
                                 "field", "model_shard")
                    or not args.smoke]

    print("name,us_per_call,derived")
    for name in selected:
        benches[name]()

    if args.json:
        with open(args.json, "w") as f:
            json.dump([{"name": n, "us_per_call": us, "derived": d}
                       for n, us, d in ROWS], f, indent=2)
        print(f"# wrote {len(ROWS)} rows to {args.json}")


if __name__ == "__main__":
    main()
