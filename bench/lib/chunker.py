"""Offline long-read traffic: heavy-tailed reads cut into overlapping chunks.

Read lengths in bases are log-normal (median ``median_bases``, shape
``sigma``) clipped to ``[min_bases, max_bases]``.  Each read's signal is
cut into ``chunk``-sample rows that start every ``chunk - overlap``
samples; the last row of a read ends where the read ends, so it overlaps
its neighbour by more, and only a read shorter than a chunk is zero-filled.
Each row carries the number of read samples it adds to the rows before it,
so the rows of a read add up to its length and a sample counts once.
"""
from __future__ import annotations

import numpy as np

from bench.lib import signals


def row_starts(n: int, chunk: int, overlap: int) -> list:
    """Start of every row of a read of ``n`` samples."""
    if n <= chunk:
        return [0]
    starts = list(range(0, n - chunk, chunk - overlap))
    return starts + [n - chunk]


def long_read_chunks(rng: np.random.Generator, traffic: dict, spec: dict):
    """Rows of at least ``traffic['pool_rows']`` chunks, in whole reads.

    Returns ``(rows (n, chunk) float32, new_samples (n,) int64)``.
    """
    chunk, overlap = traffic["chunk"], traffic["overlap"]
    want = traffic["pool_rows"]
    seqs, n_rows = [], 0
    mean_spb = spec.get("mean_dwell", 2 * spec.get("dwell", 1))
    while n_rows < want:
        ln = int(np.clip(rng.lognormal(np.log(traffic["median_bases"]),
                                       traffic["sigma"]),
                         traffic["min_bases"], traffic["max_bases"]))
        seqs.append(rng.integers(1, 5, size=ln).astype(np.int32))
        n_rows += len(row_starts(int(ln * mean_spb), chunk, overlap))
    signal, offsets = signals.encode(rng, seqs, spec)
    rows, counted = [], []
    for m in range(len(seqs)):
        sig = signal[offsets[m]:offsets[m + 1]]
        end = 0
        for start in row_starts(len(sig), chunk, overlap):
            piece = sig[start:start + chunk]
            row = np.zeros(chunk, np.float32)
            row[:len(piece)] = piece
            rows.append(row)
            counted.append(start + len(piece) - end)
            end = start + len(piece)
    return np.stack(rows), np.asarray(counted, np.int64)
