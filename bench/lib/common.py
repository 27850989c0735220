"""What every driver shares: set-up phases, compile events, the device,
tracing of the measured window and the comparison helpers."""
from __future__ import annotations

import contextlib
import os
import shutil
import time

import jax
import jax.monitoring
import numpy as np

from bench.lib import trace as trace_lib


class Clock:
    """Set-up phases from process start, and compile events all along."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.phases: dict = {}
        self.cache_hits = self.cache_misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) + (
                time.perf_counter() - t)

    def since_start(self) -> float:
        return time.perf_counter() - self.t0


def require(obj, what: str, *names: str) -> None:
    """Fail loudly where the program no longer has an attribute that the
    harness reaches beyond ``build``, ``step()``, ``records`` and the
    telemetry's stage times, rather than measure something else."""
    missing = [n for n in names if not hasattr(obj, n)]
    if missing:
        raise RuntimeError(
            f"bench: {what} ({type(obj).__name__}) has no "
            f"{', '.join(missing)}; the harness needs "
            f"{', '.join(names)} of it (tests/bench/test_bench_surfaces.py)")


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def traced_stages(telemetry) -> None:
    """Open a profiler annotation ``stage.<name>`` inside each of the
    telemetry's stage timers (traced runs only): the program's own host
    stages then appear on the profiler's clock."""
    require(telemetry, "the engine's telemetry", "stage", "stage_s")
    stage = telemetry.stage

    @contextlib.contextmanager
    def wrapped(name):
        with jax.profiler.TraceAnnotation(f"stage.{name}"), stage(name):
            yield

    telemetry.stage = wrapped


@contextlib.contextmanager
def profiled(logdir: str | None):
    """Profile the block into ``logdir`` (None: do nothing)."""
    if logdir is None:
        yield
        return
    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def reduce_trace(logdir: str, devices) -> tuple[trace_lib.Trace, dict]:
    tr = trace_lib.load(trace_lib.find_xplane(logdir))
    ids = [d.id for d in devices]
    return tr, trace_lib.reduce(tr, ids)


def memory_peak(devices) -> dict:
    """The fullest device's peaks: buffers in use, and the scratch that the
    TPU runtime reserves for the programs' temporaries apart from them (a
    batched forward's activations are counted there, not in use)."""
    best = {"peak_bytes_in_use": 0, "peak_bytes_reserved": 0}
    for d in devices:
        stats = d.memory_stats() or {}
        cur = {k: int(stats.get(k, 0)) for k in best}
        if sum(cur.values()) > sum(best.values()):
            best = cur
    return best


def edit_distances(a, la, b, lb) -> np.ndarray:
    """Row-wise Levenshtein distance between ``a[r, :la[r]]`` and
    ``b[r, :lb[r]]``, vectorised over rows."""
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


def token_mismatch(got: list, want: list) -> tuple[float, int, int]:
    """Sum of edit distances over the sum of reference tokens, the number
    of reference tokens, and the number of sequences that differ."""
    diff = [i for i, (g, w) in enumerate(zip(got, want))
            if len(g) != len(w) or not np.array_equal(g, w)]
    n_ref = int(sum(len(w) for w in want))
    if not diff:
        return 0.0, n_ref, 0
    dist = 0
    # pad per group of similar length to keep the DP small
    diff.sort(key=lambda i: max(len(got[i]), len(want[i])))
    for k in range(0, len(diff), 256):
        grp = diff[k:k + 256]
        n = max(max(len(got[i]) for i in grp), 1)
        m = max(max(len(want[i]) for i in grp), 1)
        a = np.zeros((len(grp), n), np.int64)
        b = np.zeros((len(grp), m), np.int64)
        la = np.array([len(got[i]) for i in grp])
        lb = np.array([len(want[i]) for i in grp])
        for r, i in enumerate(grp):
            a[r, :la[r]] = got[i]
            b[r, :lb[r]] = want[i]
        dist += int(edit_distances(a, la, b, lb).sum())
    return dist / max(n_ref, 1), n_ref, len(diff)
