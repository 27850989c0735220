"""Serving launcher — the one CLI entrypoint for every streaming workload.

Routes through ``repro.engine.build``; pick a workload and a preset:

  PYTHONPATH=src python -m repro.launch.serve --workload lm_decode \
      --arch qwen3-4b --smoke --requests 12 --slots 4
  PYTHONPATH=src python -m repro.launch.serve --workload basecall \
      --preset smoke --requests 32
  PYTHONPATH=src python -m repro.launch.serve --workload adaptive_sampling \
      --preset smoke --requests 16
  PYTHONPATH=src python -m repro.launch.serve --workload pathogen_pipeline \
      --requests 4

Discovery: ``--list-workloads`` prints every buildable workload,
``--list-presets <workload>`` its preset table (name + keyword bundle) —
and an unknown ``--workload``/``--preset`` fails with a ``ValueError``
naming the available options instead of a bare ``KeyError``.

Fleet mode (see :mod:`repro.fleet`): ``--fleet SPEC.json`` serves several
tenants on one mesh from a spec file::

    {"mesh": "auto",
     "tenants": [
       {"name": "lab-a", "workload": "adaptive_sampling",
        "preset": "flowcell_smoke", "weight": 2},
       {"name": "lab-b", "workload": "basecall", "preset": "smoke",
        "requests": 32}]}

Field mode (see :mod:`repro.field`): ``--field SPEC.json`` runs the
end-to-end field deployment — N edge sequencers uplinking compressed
read frames through a lossy channel to one Fleet-hosted aggregator —
where the spec file holds :class:`repro.field.FieldSpec` fields::

    {"n_devices": 8, "n_infected": 2, "n_reads": 32, "seed": 0}

Observability flags (see :mod:`repro.obs`):

  --trace PATH       export a Chrome trace-event JSON of the run (open at
                     https://ui.perfetto.dev)
  --timeseries PATH  stream per-interval delta snapshots as JSONL
  --monitor          live TTY dashboard (bases/s sparkline, occupancy,
                     moving counters) while the run drains
  --profile-dir DIR  capture a jax.profiler device trace around the run
"""
from __future__ import annotations

import argparse
import json

import numpy as np

import repro.engine as engine_api


def _drive_lm_decode(eng, args, rng) -> dict:
    from repro.engine.lm import Request
    for uid in range(args.requests):
        eng.submit(Request(
            uid=uid, prompt=rng.integers(1, eng.cfg.vocab_size, 4),
            max_new_tokens=args.new_tokens))
    return eng.drain()


def _drive_basecall(eng, args, rng) -> dict:
    eng.submit(rng.normal(size=(args.requests, eng.chunk)).astype(np.float32))
    return eng.drain()


def _drive_adaptive_sampling(eng, args, rng) -> dict:
    for i in range(args.requests):
        eng.submit(rng.normal(size=8 * eng.runtime.chunk_samples
                              ).astype(np.float32),
                   read_id=i, on_target=bool(i % 2))
    return eng.drain()


def _drive_pathogen_pipeline(eng, args, rng) -> dict:
    for _ in range(args.requests):
        eng.submit(rng.normal(size=(8, 512)).astype(np.float32))
    return eng.drain()


_DRIVERS = {
    "lm_decode": _drive_lm_decode,
    "basecall": _drive_basecall,
    "adaptive_sampling": _drive_adaptive_sampling,
    "pathogen_pipeline": _drive_pathogen_pipeline,
}


def _submit_tenant_work(fleet, tenant, spec, rng) -> None:
    """Queue one tenant's requests per its workload's input shape (a
    source-fed flowcell tenant feeds itself and takes none)."""
    n = int(spec.get("requests", 12))
    workload = tenant.workload
    if workload == "adaptive_sampling":
        eng = tenant.engine
        if eng.flowcell is not None:
            return
        for i in range(n):
            from repro.realtime import SimulatedRead
            sig = rng.normal(size=8 * eng.runtime.chunk_samples
                             ).astype(np.float32)
            tenant.submit(SimulatedRead(signal=sig, read_id=i,
                                        on_target=bool(i % 2)))
    elif workload == "lm_decode":
        from repro.engine.lm import Request
        vocab = tenant.engine.cfg.vocab_size
        for uid in range(n):
            tenant.submit(Request(uid=uid,
                                  prompt=rng.integers(1, vocab, 4),
                                  max_new_tokens=int(
                                      spec.get("new_tokens", 8))))
    elif workload == "basecall":
        chunk = tenant.engine.chunk
        for _ in range(n):
            tenant.submit(rng.normal(size=chunk).astype(np.float32))
    else:
        for _ in range(n):
            tenant.submit(rng.normal(size=(8, 512)).astype(np.float32))


def _run_fleet(args) -> dict:
    """``--fleet SPEC.json``: many tenants, one mesh, one drained report."""
    from repro.fleet import Fleet
    with open(args.fleet) as f:
        spec = json.load(f)
    fleet = Fleet(mesh=spec.get("mesh"), trace=args.trace is not None,
                  max_pending=int(spec.get("max_pending", 256)))
    rng = np.random.default_rng(args.seed)
    tenants = []
    for t in spec["tenants"]:
        tenant = fleet.add_tenant(
            t["name"], t["workload"], t.get("preset", "default"),
            weight=float(t.get("weight", 1.0)),
            priority=int(t.get("priority", 0)),
            max_pending=t.get("max_pending"),
            **t.get("overrides", {}))
        tenants.append((tenant, t))
    for tenant, t in tenants:
        _submit_tenant_work(fleet, tenant, t, rng)
    report = fleet.drain()
    if args.trace is not None:
        fleet.export_trace(args.trace)
        print(f"trace -> {args.trace} (open at https://ui.perfetto.dev)")
    if args.json:
        print(json.dumps(report, default=float, indent=2))
    else:
        fl = report["fleet"]
        print(f"fleet: {fl['n_tenants']} tenants, {fl['ticks']} ticks, "
              f"fairness_ratio={fl['fairness_ratio']:.3f}")
        for name, ts in report["tenants"].items():
            print(f"  {name:16s} ticks={ts['ticks']:<6d} "
                  f"share={ts['tick_share']:.3f} "
                  f"completed={ts.get('completed', 0)} "
                  f"p99={ts.get('p99_ms', 0.0):.2f}ms")
    return report


def _run_field(args) -> dict:
    """``--field SPEC.json``: the end-to-end field surveillance drill."""
    from repro.field import FieldSpec, run_field_scenario
    with open(args.field) as f:
        spec = FieldSpec(**json.load(f))
    res = run_field_scenario(spec, trace_path=args.trace)
    if args.json:
        print(json.dumps(res, default=float, indent=2))
    else:
        ob, wire, cons = res["outbreak"], res["wire"], res["conservation"]
        print(f"field: {spec.n_devices} devices ({spec.n_infected} "
              f"infected), {res['ticks']} ticks")
        print(f"  outbreak   detected={ob['detected']} "
              f"latency_ticks={ob['latency_ticks']} "
              f"decoy_absent={ob['decoy_absent']}")
        print(f"  wire       {wire['bytes_on_wire']} B vs "
              f"{wire['raw_signal_bytes_sequenced']} B raw signal "
              f"({wire['reduction_vs_sequenced']:.1f}x; read path "
              f"{wire['read_path_reduction']:.1f}x)")
        print(f"  conserved  exact={cons['per_device_exact']} "
              f"reads={cons['reads_ingested_unique']}"
              f"/{cons['accepted_reads_sum']} "
              f"dup={cons['dup_frames_detected']} "
              f"late={cons['late_frames']}")
        if args.trace:
            print(f"trace -> {args.trace} "
                  f"(open at https://ui.perfetto.dev)")
    return res


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="lm_decode")
    ap.add_argument("--preset", default="default")
    ap.add_argument("--list-workloads", action="store_true",
                    help="print buildable workloads and exit")
    ap.add_argument("--list-presets", default=None, metavar="WORKLOAD",
                    help="print a workload's presets and exit")
    ap.add_argument("--fleet", default=None, metavar="SPEC.json",
                    help="multi-tenant mode: serve every tenant in the "
                         "spec file on one mesh (see repro.fleet)")
    ap.add_argument("--field", default=None, metavar="SPEC.json",
                    help="field mode: run the N-device edge deployment "
                         "described by the FieldSpec JSON (see repro.field)")
    ap.add_argument("--requests", type=int, default=12,
                    help="requests / chunks / reads to drive through")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="print the telemetry summary as JSON")
    # lm_decode knobs (map onto builder overrides)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel degree: shard the model over a "
                         "(data=1, model=N) mesh (see repro.distributed.tp)")
    ap.add_argument("--ckpt", default=None, metavar="DIR",
                    help="load lm_decode params from a checkpoint dir; a "
                         "format:\"sharded\" checkpoint (from "
                         "scripts/checkpoint_converter.py) loads "
                         "pre-partitioned")
    ap.add_argument("--ckpt-step", type=int, default=None,
                    help="checkpoint step to load (default: latest)")
    # observability (repro.obs)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export a Chrome trace-event JSON of the run")
    ap.add_argument("--timeseries", default=None, metavar="PATH",
                    help="stream per-interval delta snapshots as JSONL")
    ap.add_argument("--monitor", action="store_true",
                    help="live TTY dashboard while the run drains")
    ap.add_argument("--interval", type=float, default=0.5,
                    help="time-series / dashboard snapshot interval (s)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace around the run")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.list_workloads:
        for w in engine_api.workloads():
            print(w)
        return
    if args.list_presets is not None:
        for name, kw in sorted(engine_api.presets(args.list_presets).items()):
            pretty = ", ".join(f"{k}={v!r}" for k, v in sorted(kw.items()))
            print(f"{name:16s} {pretty}" if pretty else name)
        return
    if args.fleet is not None:
        _run_fleet(args)
        return
    if args.field is not None:
        _run_field(args)
        return

    overrides: dict = {"seed": args.seed}
    if args.arch is not None:
        overrides["arch"] = args.arch
    if args.workload == "lm_decode":
        overrides["smoke"] = args.smoke
        if args.tp is not None:
            overrides["mesh"] = args.tp
        if args.ckpt is not None:
            overrides["ckpt_dir"] = args.ckpt
            if args.ckpt_step is not None:
                overrides["ckpt_step"] = args.ckpt_step
    if args.slots is not None:
        overrides["slots"] = args.slots
    if args.max_len is not None:
        overrides["max_len"] = args.max_len
    if args.trace is not None:
        overrides["trace"] = True

    eng = engine_api.build(args.workload, preset=args.preset, **overrides)
    tel = eng.telemetry
    if args.timeseries or args.monitor:
        from repro.obs import TimeSeriesExporter
        tel.exporter = TimeSeriesExporter(
            tel, scheduler=eng.scheduler, interval_s=args.interval,
            path=args.timeseries, dashboard=args.monitor)
    rng = np.random.default_rng(args.seed)
    from repro.obs import jax_profile_window
    with jax_profile_window(args.profile_dir):
        report = _DRIVERS[args.workload](eng, args, rng)
    if tel.exporter is not None:
        tel.exporter.close()
    if args.trace is not None:
        doc = tel.tracer.export_chrome(args.trace)
        n = sum(1 for e in doc["traceEvents"] if e.get("ph") != "M")
        print(f"trace: {n} events -> {args.trace} "
              f"(open at https://ui.perfetto.dev)")
    if args.json:
        print(json.dumps(report, default=float, indent=2))
    else:
        print(f"workload={args.workload} preset={args.preset}")
        for k in ("completed", "steps", "dispatches", "p50_ms", "p99_ms",
                  "bases_per_s", "samples_per_s", "tokens_per_s",
                  "signal_saved_frac", "wall_s"):
            v = report.get(k, 0)
            print(f"  {k:18s} {v:.3f}" if isinstance(v, float)
                  else f"  {k:18s} {v}")


if __name__ == "__main__":
    main()
