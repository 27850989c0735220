"""Basecall engine: batched streaming basecalls (the MAT serving path).

Raw signal chunks stream in per channel; chunks are batched across
channels, basecalled, CTC-decoded and returned with per-dispatch latency
accounting — Sec II's "real-time" requirement made measurable.

Latency fix vs the old ``BasecallServer``: the whole-batch ``dt`` used to
be appended once per row, so p50/p99 reported the batch latency duplicated
``batch`` times and half-full tail batches skewed the distribution.  The
engine records **one observation per dispatch**, weighted by the rows the
dispatch served.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.base import EngineBase
from repro.engine.registry import register
from repro.kernels import fabric as fabric_mod


class BasecallEngine(EngineBase):
    """Fixed-batch basecall dispatch over a queue of signal rows."""

    workload = "basecall"

    def __init__(self, params, bc_cfg, *, batch: int, chunk: int,
                 use_kernel=fabric_mod.UNSET, fabric=None, trace=False):
        from repro.core import basecaller, ctc
        super().__init__(slots=batch, tracer=trace)
        self.params = params
        self.cfg = bc_cfg
        self.batch = batch
        self.chunk = chunk
        # kernel placement: one fabric policy for the whole engine, resolved
        # here and carried in the basecaller's jit static args (``use_kernel=``
        # remains a deprecated shim)
        self.fabric = fabric_mod.as_policy(fabric_mod.legacy_policy(
            "BasecallEngine", use_kernel, fabric=fabric))
        self._apply = functools.partial(
            basecaller.apply, cfg=bc_cfg, fabric=self.fabric)
        self._decode = jax.jit(ctc.greedy_decode)
        # undrained decoded reads; serve() consumes the slice it produced
        self.reads: list[np.ndarray] = []

    def submit(self, signal_rows: np.ndarray, **_) -> None:
        """Enqueue one or more ``(chunk,)`` signal rows."""
        rows = np.asarray(signal_rows, np.float32)
        if rows.ndim == 1:
            rows = rows[None]
        for row in rows:
            self.scheduler.submit(row)

    def step(self) -> bool:
        """Dispatch one batch (up to ``self.batch`` queued rows)."""
        admitted = self.scheduler.admit()
        if not admitted:
            return False
        t_wall = time.perf_counter()
        chunk_rows = np.stack([row for _, row in admitted])
        t0 = time.perf_counter()
        with self.telemetry.scope():
            with self.telemetry.stage("basecall"):
                logits = self._apply(self.params, jnp.asarray(chunk_rows))
            with self.telemetry.stage("decode"):
                tokens, lens = self._decode(logits)
                tokens.block_until_ready()
        dt = (time.perf_counter() - t0) * 1e3
        # one latency observation per dispatch, weighted by rows served
        self.telemetry.observe_latency(dt, weight=len(chunk_rows))
        self.telemetry.dispatches += 1
        self.telemetry.steps += 1
        with self.telemetry.stage("readback"):
            for j, (slot, _) in enumerate(admitted):
                ln = int(lens[j])
                self.reads.append(np.asarray(tokens[j][:ln]))
                self.telemetry.bases += ln
                self.telemetry.completed += 1
                self.scheduler.release(slot)
        self.telemetry.samples += int(chunk_rows.size)
        self.telemetry.wall_s += time.perf_counter() - t_wall
        self.telemetry.gauge("queue_depth", self.scheduler.pending)
        return True

    def serve(self, signal_chunks: np.ndarray) -> list[np.ndarray]:
        """Convenience: submit ``(N, chunk)`` rows, drain, return the reads
        produced by this call (decoded token arrays, in submit order).

        Consumes the returned reads from ``self.reads`` so a long-running
        server does not accumulate every read ever called; ``step``-level
        callers own draining ``self.reads`` themselves."""
        mark = len(self.reads)
        self.submit(signal_chunks)
        self.drain()
        out = self.reads[mark:]
        del self.reads[mark:]
        return out


@register("basecall", presets={
    "default": {"batch": 16, "chunk": 2048},
    "smoke": {"batch": 4, "chunk": 512},
    # the paper's edge configuration: weights stored int8 once at build,
    # every dispatch on the fixed-point MAC path (calibrated activations)
    "edge_int8": {"batch": 16, "chunk": 2048, "quantize": "int8"},
})
def build_basecall(params=None, cfg=None, *, batch: int, chunk: int,
                   quantize: str | None = None,
                   use_kernel=fabric_mod.UNSET, fabric=None, seed: int = 0,
                   trace=False):
    """Builder: supply trained (params, cfg) or get a fresh paper-shaped CNN.

    ``quantize="int8"`` (the ``edge_int8`` preset) calibrates and quantizes
    the weights once at build; already-quantized params pass through.
    ``trace`` enables span tracing (True, or a shared Tracer)."""
    from repro.core import basecaller as bc
    from repro.engine.base import quantize_edge_params
    if cfg is None:
        cfg = bc.BasecallerConfig()
    if params is None:
        params = bc.init(jax.random.key(seed), cfg)
    if quantize is not None:
        params = quantize_edge_params(params, cfg, scheme=quantize,
                                      chunk=chunk, seed=seed)
    return BasecallEngine(params, cfg, batch=batch, chunk=chunk,
                          use_kernel=use_kernel, fabric=fabric, trace=trace)
