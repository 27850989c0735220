#!/usr/bin/env python3
"""The control of ``correct``: runs of a cell whose comparison must fail.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30

Each seed is one whole run of the cell through ``bench/run.py``'s own
``main``, at the cell's own size and load, in one process, with the plain
reference at the configuration's ``control`` precision (the operand
precision below the one the configuration states) put in the program's
place for the comparison.  Each run prints the harness's own result line,
whose ``correct`` must come out false, and an ``info`` line that also holds
the program's readings of the same window (``program_checks``): the lower
and upper readings the limits are set from.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import bench.run as bench_run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    _, _, cfg, _, _, _ = bench_run.load_cell(ROOT, args.workload)
    operands = cfg["control"]
    results = []
    for seed in args.seeds.split(","):
        results.append(bench_run.main(
            ["--workload", args.workload, "--seed", seed, "--seconds",
             str(args.seconds), "--trace", "0"], control=operands))
    failed = sum(not r["correct"] for r in results)
    print(f"control {operands}: {failed} of {len(results)} runs not "
          f"correct", file=sys.stderr, flush=True)
    return 0 if failed == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
