"""Seed-and-extend alignment (paper Sec II-B.2): FM-index seeds vetted by
banded dynamic-programming extension on the ED engine.

"The following step, extension, vets promising seeds by computing an
approximate dynamic programming (DP) alignment ... DP — like DL — represents
a generalizable algorithmic structure that favours scalable,
hardware-accelerated implementation."

Pipeline per read batch:
  1. sample k-mer seeds at fixed offsets across the read,
  2. batched FM-index backward search (fm_index.backward_search),
  3. diagonal voting: each seed hit implies candidate alignment start
     (hit_pos - seed_offset); hits vote into coarse diagonal buckets,
  4. banded-NW extension (kernels/edit_distance.banded_align) of the read
     against the best candidate windows — the ED-engine workload,
  5. best (position, score) per read + score gap as a mapping-quality proxy.

Everything after index lookup is fixed-shape and jit-friendly; candidate
count is capped (``max_candidates``) exactly like hardware aligners cap
extension queues.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fm_index
from repro.kernels import fabric as fabric_mod
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class AlignConfig:
    seed_len: int = 12
    seed_stride: int = 8
    max_hits_per_seed: int = 8
    max_candidates: int = 4
    band: int = 24
    match: int = 2
    mismatch: int = -4
    gap: int = -2
    min_score_frac: float = 0.5  # accept if score > frac * max_possible


@dataclasses.dataclass
class AlignmentResult:
    positions: np.ndarray   # (R,) best ref start, -1 if unaligned
    scores: np.ndarray      # (R,) banded NW score
    mapq: np.ndarray        # (R,) score gap to runner-up (proxy)
    accepted: np.ndarray    # (R,) bool
    voted: np.ndarray       # (R,) bool — at least one seed hit (row voted)
    upload_bytes: int       # index bytes sent to the device for this call


def _extract_seeds(reads: jnp.ndarray, cfg: AlignConfig):
    """(R, L) -> (R, S, k) seeds + (S,) offsets."""
    r, l = reads.shape
    offsets = np.arange(0, l - cfg.seed_len + 1, cfg.seed_stride)
    seeds = jnp.stack(
        [jax.lax.dynamic_slice_in_dim(reads, int(o), cfg.seed_len, axis=1)
         for o in offsets], axis=1)
    return seeds, offsets


def _vote_candidates(hits: np.ndarray, offsets: np.ndarray, genome_len: int,
                     cfg: AlignConfig):
    """hits: (R, S, H) genome positions (-1 invalid) -> (R, C) candidate
    starts by diagonal voting (host-side numpy; small and irregular)."""
    r, s, h = hits.shape
    starts = hits - offsets[None, :, None]
    starts = np.where(hits >= 0, starts, -(10 ** 9))
    bucket = cfg.band  # diagonal tolerance
    cands = np.full((r, cfg.max_candidates), -1, np.int64)
    for i in range(r):
        vals = starts[i][starts[i] > -(10 ** 8)]
        if len(vals) == 0:
            continue
        keys, votes = np.unique(vals // bucket, return_counts=True)
        order = np.argsort(-votes)
        top = keys[order[: cfg.max_candidates]]
        for j, b in enumerate(top):
            member = vals[vals // bucket == b]
            pos = int(np.median(member))
            cands[i, j] = min(max(pos, 0), max(genome_len - 1, 0))
    return cands


def align_reads(index: fm_index.FMIndex, genome: np.ndarray,
                reads: np.ndarray, cfg: AlignConfig = AlignConfig(),
                *, interpret=fabric_mod.UNSET, fabric=None,
                stage=contextlib.nullcontext) -> AlignmentResult:
    """Align a batch of reads against ``genome`` (1..4 tokens).

    The banded-extension placement (ED kernel vs oracle) comes from the
    compute-fabric policy; ``interpret=`` is a deprecated shim.

    ``stage`` is a context-manager factory called with each phase's name
    (``map.upload``, ``map.search``, ``map.vote``, ``map.extend``, in that
    order and covering the call), e.g. ``Telemetry.stage``; the default
    names nothing.  No phase waits on the device to close: a transfer
    still in flight finishes inside the phase that reads its result.
    """
    fabric = fabric_mod.legacy_policy("seed_extend.align_reads",
                                      interpret=interpret, fabric=fabric)
    r, l = reads.shape
    with stage("map.upload"):
        arrays = index.device_arrays()
        upload_bytes = sum(int(a.nbytes) for a in arrays.values())
    with stage("map.search"):
        seeds, offsets = _extract_seeds(jnp.asarray(reads), cfg)
        s = seeds.shape[1]
        _, pos = fm_index.backward_search(
            arrays, seeds.reshape(r * s, cfg.seed_len),
            max_hits=cfg.max_hits_per_seed)
        hits = np.asarray(pos).reshape(r, s, cfg.max_hits_per_seed)
    with stage("map.vote"):
        voted = (hits >= 0).reshape(r, -1).any(axis=1)
        cands = _vote_candidates(hits, offsets, index.length, cfg)
    with stage("map.extend"):
        # window extraction (host gather; read length + band slack)
        wlen = l + 2 * cfg.band
        gpad = np.concatenate([
            np.zeros(cfg.band, np.int32), np.asarray(genome, np.int32),
            np.zeros(wlen, np.int32)])  # zeros mismatch every base
        win_idx = (np.clip(cands, 0, None)[..., None]
                   + np.arange(wlen)[None, None, :])
        windows = gpad[win_idx]  # (R, C, wlen); cand -1 -> leading pad

        # banded extension on the ED engine: read vs each candidate window
        q = jnp.asarray(np.repeat(reads, cfg.max_candidates, axis=0))
        t = jnp.asarray(windows.reshape(r * cfg.max_candidates, wlen))
        scores = ops.banded_align(
            q, t, band=2 * cfg.band, match=cfg.match,
            mismatch=cfg.mismatch, gap=cfg.gap, local=True, fabric=fabric)
        scores = np.asarray(scores).reshape(r, cfg.max_candidates)
        scores = np.where(cands >= 0, scores, -(10 ** 9))

        best = np.argmax(scores, axis=1)
        best_score = scores[np.arange(r), best]
        sorted_sc = np.sort(scores, axis=1)
        gap2 = best_score - (sorted_sc[:, -2] if cfg.max_candidates > 1
                             else np.zeros(r))
        positions = cands[np.arange(r), best]
        max_possible = cfg.match * l
        accepted = (best_score > cfg.min_score_frac * max_possible)
        positions = np.where(accepted, positions, -1)
    return AlignmentResult(
        positions=positions,
        scores=best_score,
        mapq=np.clip(gap2, 0, 60),
        accepted=accepted,
        voted=voted,
        upload_bytes=upload_bytes,
    )
