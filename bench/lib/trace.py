"""From a profiler trace to busy/idle time, module time and idle gaps.

A traced run writes ``<logdir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load` keeps three things of it:

* per device plane (``/device:TPU:<n>``), the intervals of its ``XLA Ops``
  line (each an operation running on the device) and of its
  ``XLA Modules`` line (each an execution of a compiled program, named
  after the jitted function, e.g. ``jit_step(123)``);
* the host's annotations (``jax.profiler.TraceAnnotation``) whose names
  start with one of the benchmark's prefixes.

:func:`reduce` then works inside the window that the ``bench.window``
annotation spans: busy is the union of a device's operation intervals,
idle is the rest of the window, and each stretch of idle time is laid on
the innermost host annotation open over it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIXES = ("bench.", "stage.")
WINDOW = "bench.window"


@dataclasses.dataclass
class Trace:
    ops: dict           # device id -> list of (name, start_ns, end_ns)
    modules: dict       # device id -> list of (name, start_ns, end_ns)
    host: list          # (name, start_ns, end_ns) benchmark annotations


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dest = ops if line.name == OPS_LINE else modules
                dest.setdefault(int(m.group(1)), []).extend(
                    (e.name, float(e.start_ns),
                     float(e.start_ns) + float(e.duration_ns))
                    for e in line.events)
            elif not m:
                host.extend(
                    (e.name, float(e.start_ns),
                     float(e.start_ns) + float(e.duration_ns))
                    for e in line.events if e.name.startswith(HOST_PREFIXES))
    return Trace(ops=ops, modules=modules, host=host)


def op_name(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.30 = s32[...]
    fusion(...)`` -> ``fusion.30``."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals, lo: float, hi: float) -> list:
    """Merged intervals clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(e, hi)) for _, s, e in intervals
                if e > lo and s < hi)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window(trace: Trace) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in trace.host if n == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    return spans[-1]


def module_time(trace: Trace, prefix: str, lo: float, hi: float,
                devices=None) -> tuple[float, int]:
    """Seconds of device time of every execution of the modules whose name
    starts with ``prefix``, summed over devices, and the number of
    executions on one device."""
    pat = re.compile(re.escape(prefix) + r"(\(|$|\.)")
    total, counts = 0.0, []
    for dev, events in trace.modules.items():
        if devices is not None and dev not in devices:
            continue
        sel = [(s, e) for n, s, e in events
               if pat.match(n) and s >= lo and e <= hi]
        total += sum(e - s for s, e in sel) * 1e-9
        counts.append(len(sel))
    return total, (max(counts) if counts else 0)


def innermost(host: list, lo: float, hi: float) -> list:
    """``[lo, hi]`` cut into pieces, each with the innermost benchmark
    annotation open over it (the one that started last), or
    ``host.unannotated``."""
    cuts = sorted({lo, hi, *(t for _, s, e in host for t in (s, e)
                            if lo < t < hi)})
    events = sorted(((s, e, n) for n, s, e in host if n != WINDOW),
                    key=lambda x: x[0])
    out, open_, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(events) and events[k][0] <= a:
            open_.append(events[k])
            k += 1
        open_ = [ev for ev in open_ if ev[1] > a]
        name = max(open_)[2] if open_ else "host.unannotated"
        if out and out[-1][1] == name and out[-1][0][1] == a:
            out[-1] = ((out[-1][0][0], b), name)
        else:
            out.append(((a, b), name))
    return out


def reduce(trace: Trace, devices, top: int = 10) -> dict:
    """Busy and idle seconds (mean over ``devices``), the window's length,
    the operations that took most device time, and idle time by the host
    annotation open over each part of it (gaps of the first device)."""
    lo, hi = window(trace)
    busy = []
    for dev in devices:
        merged = union(trace.ops.get(dev, []), lo, hi)
        busy.append(sum(e - s for s, e in merged))
    by_op: dict = {}
    for dev in devices:
        for n, s, e in trace.ops.get(dev, []):
            if e > lo and s < hi:
                n = op_name(n)
                by_op[n] = by_op.get(n, 0.0) + (min(e, hi) - max(s, lo))
    merged = union(trace.ops.get(devices[0], []), lo, hi)
    gaps, t = [], lo
    for s, e in merged:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    idle_by: dict = {}
    for (s, e), name in innermost(trace.host, lo, hi):
        for gs, ge in gaps:
            if ge <= s:
                continue
            if gs >= e:
                break
            idle_by[name] = idle_by.get(name, 0.0) + (min(e, ge)
                                                      - max(s, gs))
    n_dev = len(devices)
    window_s = (hi - lo) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": float(np.mean(busy)) * 1e-9,
        "device_ops": [[n, v * 1e-9 / n_dev] for n, v in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, v * 1e-9] for n, v in
                      sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]],
        "bounds_ns": (lo, hi),
    }
