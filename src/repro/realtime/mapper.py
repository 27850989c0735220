"""Map called read prefixes against an enrichment target panel.

Reuses the repo's offline alignment stack end-to-end — FM-index backward
search for seeds, diagonal voting, banded extension on the ED kernel — but
drives it with the short, error-containing prefixes the streaming basecaller
emits.  The mapper's shapes are fixed (a full channel-batch of fixed-length
prefixes every call), so the jitted seed search and banded-align kernels
compile exactly once for the lifetime of a run.
"""
from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

from repro.core import fm_index, seed_extend
from repro.kernels import fabric as fabric_mod


@dataclasses.dataclass(frozen=True)
class TargetPanel:
    """Reference genome plus the intervals to enrich for."""
    reference: np.ndarray       # (N,) 1..4 tokens
    target_mask: np.ndarray     # (N,) bool, True inside enrichment targets
    intervals: tuple            # ((start, end), ...) half-open

    @staticmethod
    def build(reference: np.ndarray, intervals) -> "TargetPanel":
        reference = np.asarray(reference, np.int32)
        mask = np.zeros(len(reference), bool)
        clean = []
        for start, end in intervals:
            start, end = max(int(start), 0), min(int(end), len(reference))
            mask[start:end] = True
            clean.append((start, end))
        return TargetPanel(reference=reference, target_mask=mask,
                           intervals=tuple(clean))

    @property
    def target_frac(self) -> float:
        return float(self.target_mask.mean())


@dataclasses.dataclass
class MapResult:
    mapped: np.ndarray      # (R,) bool — confident alignment found
    on_target: np.ndarray   # (R,) bool — alignment lands in a target
    positions: np.ndarray   # (R,) int  — best reference start (-1 unmapped)
    mapq: np.ndarray        # (R,) float — score gap to runner-up (0..60)
    scores: np.ndarray      # (R,) int  — banded-SW score of the best hit
    voted: np.ndarray       # (R,) bool — at least one seed hit (row voted)
    upload_bytes: int       # index bytes sent to the device for this call


# Prefixes are short (~50 bases) and noisy: denser/shorter seeds than the
# offline aligner, a generous band for CTC indels, and a lower score floor.
PREFIX_ALIGN_CFG = seed_extend.AlignConfig(
    seed_len=10, seed_stride=6, max_hits_per_seed=8, max_candidates=4,
    band=16, min_score_frac=0.35)


class PrefixMapper:
    """Fixed-shape batched prefix->panel mapping for the decision loop."""

    def __init__(self, panel: TargetPanel,
                 align_cfg: seed_extend.AlignConfig = PREFIX_ALIGN_CFG,
                 *, interpret=fabric_mod.UNSET, fabric=None):
        self.panel = panel
        self.cfg = align_cfg
        self.index = fm_index.FMIndex.build(panel.reference)
        # placement for the banded-extension kernel; ``interpret=`` is a
        # deprecated shim translated into a policy override
        self._fabric = fabric_mod.legacy_policy("PrefixMapper",
                                                interpret=interpret,
                                                fabric=fabric)

    def map_prefixes(self, prefixes: np.ndarray, *,
                     stage=contextlib.nullcontext) -> MapResult:
        """prefixes: (R, L) called bases (1..4; 0-padded rows are ignored by
        the caller).  R and L must stay constant across calls so the jitted
        kernels compile once.  ``stage`` names the alignment's phases
        (``map.upload`` ... ``map.extend``, see
        :func:`seed_extend.align_reads`)."""
        res = seed_extend.align_reads(self.index, self.panel.reference,
                                      np.asarray(prefixes, np.int32),
                                      self.cfg, fabric=self._fabric,
                                      stage=stage)
        pos = np.clip(res.positions, 0, len(self.panel.reference) - 1)
        on_target = np.where(res.accepted, self.panel.target_mask[pos], False)
        return MapResult(mapped=res.accepted, on_target=on_target,
                         positions=res.positions, mapq=res.mapq,
                         scores=res.scores, voted=res.voted,
                         upload_bytes=res.upload_bytes)
