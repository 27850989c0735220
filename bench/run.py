#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration and traffic files name everything else: the
driver (``bench/drivers/<driver>.py``) that runs the kind of work, and the
metric readers (``bench/metrics/<metric>.py``) that turn what the driver
observed into the cell's metrics.  Adding a cell, a configuration, a
traffic mix or a metric is adding files.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a window of at most ``TRACE_SECONDS`` runs under the
profiler and the result carries
the cell's per-layer metrics, the device's busy and window seconds and a
breakdown of device operations and idle gaps.  Every run ends with the
comparison against the plain reference that decides ``correct``: each
number compared is printed with its limit as the last lines on standard
error and under ``checks``, the last key of the result.

The run fails, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_SECONDS = 5.0


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(root: pathlib.Path, metric: str) -> pathlib.Path:
    """``bench/metrics/<metric>.py``, else the reader of the metric's name
    up to its first dot: ``mfu.ru`` and ``mfu.offline`` share ``mfu.py``."""
    own = root / "bench" / "metrics" / f"{metric}.py"
    if own.is_file():
        return own
    return root / "bench" / "metrics" / f"{metric.split('.')[0]}.py"


def load_cell(root: pathlib.Path, name: str):
    """The cell's entry, configuration, traffic and the metrics that apply
    to it, from ``BENCHMARK.json`` and the files it names."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())

    def applies(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    per_layer = [m for m in bench["per_layer"] if applies(m)]
    return bench, cell, cfg, traffic, e2e, per_layer


class Run:
    """One run of one cell: its arguments, clock and what it observed."""

    def __init__(self, args, root, cell, cfg, traffic, devices):
        from bench.lib.common import Clock
        self.seed = args.seed
        # a traced run traces a short window of its own: the trace of a
        # long one is too large to read back within a run's time
        self.seconds = min(args.seconds, TRACE_SECONDS) if args.trace \
            else args.seconds
        self.root = root
        self.cell = cell
        self.cfg = cfg
        self.traffic = traffic
        self.devices = devices
        self.clock = Clock(T0)
        self.clock.phases["init"] = self.clock.since_start()
        self.trace_dir = str(root / ".bench_out" / "trace") \
            if args.trace else None
        self.setup_s = None
        self.memory = self.memory_peak = None

    def setup_done(self):
        self.setup_s = self.clock.since_start()

    def after_window(self, win: dict):
        from bench.lib.common import memory_peak
        self.memory = memory_peak(self.devices)
        self.memory_peak = sum(self.memory.values())
        if self.trace_dir is not None:
            from bench.lib.common import reduce_trace
            used = self.devices[:win.get("chips_used", len(self.devices))]
            win["trace"], win["trace_summary"] = reduce_trace(
                self.trace_dir, used)


def enable_cache() -> str:
    """JAX's persistent compilation cache at the checkout's fixed path
    (``JAX_COMPILATION_CACHE_DIR`` where it is set), for every program
    however short its compile, so that only a cell's first run in a
    checkout compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def main(argv=None, *, root: pathlib.Path = ROOT, require_tpu: bool = True,
         compile_cache: bool = True, control: str | None = None):
    """One run of one cell.  ``control`` (``bench/control.py`` only) puts
    the plain reference at that operand precision in the program's place
    for the comparison, whose result then decides ``correct``; the
    program's own readings of the same window go to the ``info`` line."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    bench, cell, cfg, traffic, e2e, per_layer = load_cell(root,
                                                          args.workload)

    import jax
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise SystemExit(f"bench: no TPU (JAX found "
                             f"{devices[0].platform}); the benchmark runs "
                             f"on the chip only")
        if len(devices) < cell["chips"]:
            raise SystemExit(f"bench: {cell['name']} needs {cell['chips']} "
                             f"chips, JAX found {len(devices)}")
    devices = devices[:cell["chips"]]
    cache_dir = enable_cache() if compile_cache else None

    run = Run(args, root, cell, cfg, traffic, devices)
    driver = load_module(root / "bench" / "drivers"
                         / f"{traffic['driver']}.py")
    obs = driver.run(run, cfg, traffic, control=control)
    obs.update(run=run, setup_s=run.setup_s, cfg=cfg, traffic=traffic,
               device_kind=devices[0].device_kind)

    wanted = per_layer if args.trace else e2e
    metrics = {}
    for m in wanted:
        reader = load_module(reader_path(root, m["name"]))
        value = reader.read(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    check = obs["check"]
    checks = dict(check["checks"])
    if not args.trace:
        # a run whose window gave an end-to-end metric nothing to read (no
        # read decided, no batch done) did not serve the cell's traffic
        missing = [m["name"] for m in e2e if m["name"] not in metrics]
        checks["metrics_missing"] = {"value": len(missing), "limit": 0}
    correct = bool(check["attempted"]) and all(
        c["value"] <= c["limit"] for c in checks.values())
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak}
    result = {"correct": correct, "attempted": check["attempted"],
              "failed": check["failed"], "metrics": metrics,
              "device": device}
    if args.trace:
        summary = obs["trace_summary"]
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks

    info = {
        "cell": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "jax": jax.__version__, "compile_cache":
        cache_dir, "setup": {
            "setup_s": run.setup_s,
            **{f"{k}_s": v for k, v in run.clock.phases.items()},
            "cache_hits": run.clock.cache_hits,
            "cache_misses": run.clock.cache_misses},
        "compiles_in_window": obs["compiles_in_window"],
        "window_s": obs["window_s"],
        "fabric": obs["fabric"],
        "memory": run.memory,
        "pacer": obs.get("pacer"),
        "check": {k: v for k, v in check.items() if k != "checks"},
    }
    if control is not None:
        info["control"] = control
        info["program_checks"] = obs["program_check"]["checks"]
    print("info " + json.dumps(info), flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
