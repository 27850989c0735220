"""The traffic generators and the Read-Until bookkeeping that the metrics
and the check read, on hand-made inputs."""
from __future__ import annotations

import types

import numpy as np
import pytest

import benchtools  # noqa: F401  (puts the checkout on the path)
from bench.drivers import readuntil as ru
from bench.lib import chunker, signals


@pytest.mark.parametrize("n", [700, 1000, 1001, 2500, 4321])
def test_rows_cover_a_read_once(n):
    starts = chunker.row_starts(n, chunk=1000, overlap=100)
    assert starts[0] == 0
    assert starts[-1] == max(n - 1000, 0)
    assert all(b - a <= 900 for a, b in zip(starts, starts[1:]))


def test_chunk_counts_add_up_to_the_reads():
    traffic = {"chunk": 1000, "overlap": 100, "pool_rows": 12,
               "median_bases": 200, "sigma": 0.8, "min_bases": 50,
               "max_bases": 600}
    spec = {"encoder": "step", "levels": [0, 2, 4, 6, 8], "dwell": 2}
    rows, counted = chunker.long_read_chunks(np.random.default_rng(3),
                                             traffic, spec)
    assert rows.shape == (len(counted), 1000) and len(rows) >= 12
    assert (counted > 0).all() and (counted <= 1000).all()
    # the same draws again: the rows add up to the reads' samples
    rng = np.random.default_rng(3)
    total = 0
    n_rows = 0
    while n_rows < 12:
        ln = int(np.clip(rng.lognormal(np.log(200), 0.8), 50, 600))
        rng.integers(1, 5, size=ln)
        total += ln * 4
        n_rows += len(chunker.row_starts(ln * 4, 1000, 100))
    assert counted.sum() == total


def test_pool_molecules_keep_their_lengths():
    rng = np.random.default_rng(1)
    genome = signals.random_genome(rng, 2000)
    spec = {"encoder": "step", "levels": [0, 2, 4, 6, 8], "dwell": 2}
    pool = signals.molecule_pool(rng, genome, np.zeros(2000, bool), 16,
                                 (20, 40), spec)
    assert (pool.lengths() == [4 * len(s) for s in pool.seqs]).all()
    m = pool.molecule(3)
    assert np.array_equal(m[0::4], np.asarray([0, 2, 4, 6, 8],
                                               np.float32)[pool.seqs[3]])


def _source(captured_at, totals):
    pool = types.SimpleNamespace(lengths=lambda: np.asarray(totals))
    return types.SimpleNamespace(captured_at=list(captured_at),
                                 molecule_of=list(range(len(totals))),
                                 pool=pool)


def test_streamed_samples_counts_whole_frames_in_the_window():
    # chunk 8, stride 4; window = steps 2..5
    src = _source(captured_at=[0, 2, 4, 5], totals=[30, 13, 8, 40])
    last = np.array([3, 5, 9, 9])
    got = ru.streamed_samples(src, last, d0=2, d1=6, chunk=8, stride=4)
    # read 0: chunks 2, 3 -> samples 16..24 and 24..28 (30 -> 28 usable)
    # read 1: chunks 0..1 (steps 2, 3); steps 4, 5 are past its 12 usable
    # read 2: chunk 0 (step 4) and 1 (step 5, empty)
    # read 3: chunk 0 (step 5)
    assert got == (8 + 4) + 12 + 8 + 8


def test_decision_latency_runs_from_the_evidence_chunk_due_time():
    # (due, start, end, dispatched before, after, records before, after)
    calls = [(0.000, 0.000, 0.010, 0, 1, 0, 0),
             (0.064, 0.064, 0.080, 1, 2, 0, 2),   # decides step 0's reads
             (0.128, 0.130, 0.150, 2, 3, 2, 3)]   # decides step 1's read
    recs = [types.SimpleNamespace(reason=r)
            for r in ("mapped", "exhausted", "timeout")]
    lat = ru.decision_latencies(calls, recs, 0, depth=2)
    assert lat == pytest.approx([80.0, 86.0])
