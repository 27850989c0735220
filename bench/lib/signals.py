"""Signal synthesis for the benchmark's traffic, made in bulk from a seed.

The benchmark keeps its own copy of the two signal encoders the system is
fed with, so that a change to the program cannot change its inputs:

``pore``  the squiggle model: a current level per centred 5-mer, a
          geometric dwell of ``min_dwell`` + Geom samples per base (mean
          ``mean_dwell``), Gaussian noise, a slow baseline drift with its
          end pulled back to zero, then per-read median/MAD normalisation.
``step``  a noiseless code: per base ``dwell`` samples at the base's level
          then ``dwell`` samples at level 0, so every base is exactly
          ``2 * dwell`` samples.

Everything is vectorised over a whole pool of molecules at once.  The
molecules of a pool are stored back to back in one float32 array.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Pool:
    """Molecules stored back to back: molecule ``m`` is
    ``signal[offsets[m]:offsets[m + 1]]``."""
    signal: np.ndarray          # (total,) float32
    offsets: np.ndarray         # (n + 1,) int64
    seqs: list                  # per molecule, its bases (1..4)
    starts: np.ndarray          # (n,) position in the genome
    on_target: np.ndarray       # (n,) bool

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def molecule(self, m: int) -> np.ndarray:
        return self.signal[self.offsets[m]:self.offsets[m + 1]]

    def lengths(self) -> np.ndarray:
        return np.diff(self.offsets)


def pore_levels(spec: dict) -> np.ndarray:
    """(4**k,) zero-mean, unit-spread current level per k-mer."""
    rng = np.random.default_rng(spec["table_seed"])
    lv = rng.normal(0.0, 1.0, size=4 ** spec["k"])
    return (lv - lv.mean()) / lv.std()


def _kmer_index(seqs: list, k: int) -> np.ndarray:
    """Centred k-mer index of every base of every sequence, concatenated;
    each sequence is padded by its own first and last ``k // 2`` bases."""
    pad = k // 2
    parts = []
    for s in seqs:
        s = np.asarray(s, np.int64) - 1
        sp = np.concatenate([s[:pad], s, s[-pad:]]) if pad else s
        idx = np.zeros(len(s), np.int64)
        for i in range(k):
            idx = idx * 4 + sp[i:i + len(s)]
        parts.append(idx)
    return np.concatenate(parts)


def pore_encode(rng: np.random.Generator, seqs: list, spec: dict):
    """Pore-model signal of every sequence; returns (signal, offsets)."""
    levels = pore_levels(spec)
    lv = levels[_kmer_index(seqs, spec["k"])]
    nb = len(lv)
    dwell = spec["min_dwell"] + rng.geometric(
        1.0 / max(spec["mean_dwell"] - spec["min_dwell"], 1e-6), size=nb)
    base_counts = np.array([len(s) for s in seqs], np.int64)
    base_off = np.concatenate([[0], np.cumsum(base_counts)])
    samples = np.add.reduceat(dwell, base_off[:-1])
    offsets = np.concatenate([[0], np.cumsum(samples)]).astype(np.int64)
    total = int(offsets[-1])
    sig = np.repeat(lv, dwell).astype(np.float32)
    sig += rng.normal(0.0, spec["noise"], size=total).astype(np.float32)
    # drift: a random walk per molecule, restarted at each molecule's first
    # sample, with a linear ramp removed so that it ends where it began
    steps = rng.normal(0.0, spec["drift"] / np.sqrt(spec["mean_dwell"]),
                       size=total)
    walk = np.cumsum(steps)
    first = offsets[:-1]
    before = np.where(first > 0, walk[np.maximum(first - 1, 0)], 0.0)
    mol = np.repeat(np.arange(len(seqs)), samples)
    walk -= before[mol]
    pos = np.arange(total) - first[mol]
    end = walk[offsets[1:] - 1]
    walk -= end[mol] * pos / np.maximum(samples[mol] - 1, 1)
    sig += walk.astype(np.float32)
    for m in range(len(seqs)):
        seg = sig[offsets[m]:offsets[m + 1]]
        med = np.median(seg)
        mad = np.median(np.abs(seg - med)) + 1e-6
        seg -= med
        seg /= 1.4826 * mad
    return sig, offsets


def step_encode(seqs: list, spec: dict):
    """Noiseless step code of every sequence; returns (signal, offsets)."""
    levels = np.asarray(spec["levels"], np.float32)
    dwell = spec["dwell"]
    allb = np.concatenate([np.asarray(s, np.int64) for s in seqs])
    seg = np.zeros((len(allb), 2 * dwell), np.float32)
    seg[:, :dwell] = levels[allb][:, None]
    counts = np.array([len(s) for s in seqs], np.int64) * 2 * dwell
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    return seg.reshape(-1), offsets


def encode(rng: np.random.Generator, seqs: list, spec: dict):
    if spec["encoder"] == "pore":
        return pore_encode(rng, seqs, spec)
    if spec["encoder"] == "step":
        return step_encode(seqs, spec)
    raise ValueError(f"unknown encoder {spec['encoder']!r}")


def random_genome(rng: np.random.Generator, length: int) -> np.ndarray:
    return rng.integers(1, 5, size=length).astype(np.int32)


def target_panel(rng: np.random.Generator, length: int, count: int,
                 target_len: int) -> list:
    """``count`` targets of ``target_len`` bases, one at a random place in
    each of ``count`` equal stretches of the genome (never overlapping)."""
    span = length // count
    offs = rng.integers(0, span - target_len + 1, size=count)
    return [(int(i * span + o), int(i * span + o + target_len))
            for i, o in enumerate(offs)]


def target_mask(length: int, intervals) -> np.ndarray:
    mask = np.zeros(length, bool)
    for start, end in intervals:
        mask[start:end] = True
    return mask


def molecule_pool(rng: np.random.Generator, genome: np.ndarray,
                  mask: np.ndarray, n: int, read_len, spec: dict) -> Pool:
    """``n`` molecules drawn uniformly from ``genome`` with lengths uniform
    in ``read_len`` (bases, inclusive), encoded by ``spec``."""
    lo, hi = read_len
    starts = rng.integers(0, len(genome) - hi, size=n)
    lens = rng.integers(lo, hi + 1, size=n)
    seqs = [genome[s:s + ln] for s, ln in zip(starts, lens)]
    signal, offsets = encode(rng, seqs, spec)
    return Pool(signal=signal, offsets=offsets, seqs=seqs, starts=starts,
                on_target=mask[starts + lens // 2])
