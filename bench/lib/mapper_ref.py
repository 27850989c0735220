"""Plain reference of the Read-Until mapper and decision rule.

The same semantics as the deployment's prefix mapper, written directly in
numpy over an index of its own: the suffix array of the genome followed by
one sentinel ``0`` (smaller than every base), its Burrows-Wheeler
transform and the running count of each base in it.

Mapping a window of ``L`` called bases: seeds of ``seed_len`` bases every
``seed_stride`` bases; each seed is searched backward, last symbol first,
through the count table (``[lo, hi) <- C[c] + occ_c(lo), C[c] + occ_c(hi)``),
and its hits are the first ``max_hits`` suffixes of the final range in
suffix order.  A window with fewer than ``L`` called bases is zero-filled at
its tail, and a padding symbol ``0`` is searched by the same arithmetic
with ``C[0] = 0`` and the count column of base 4 (the table's last column,
which the program's ``occ[:, c - 1]`` reads at ``c = 0``), so decisions
taken on 32 to 47 bases are retaken as the program takes them.  Each hit
votes ``start = hit - offset`` into buckets of ``band`` positions; the
``max_candidates`` best-voted buckets give the candidates (the median of a
bucket's starts); a banded local alignment (Smith-Waterman, linear gaps,
cells with ``|i - j| > 2 * band`` held at 0) of the window against
``genome[cand - band : cand + L + band]`` (zeros outside the genome) scores
each; the best score and its gap to the runner-up, clipped to 0..60, are
the score and the mapping quality.  A window is mapped when its best score
exceeds ``min_score_frac * match * L``; it is on target when the genome's
target mask is set at the mapped position.

The decision rule: mapped and on target -> ACCEPT, mapped off target with
quality >= ``min_mapq`` -> EJECT, otherwise WAIT until the prefix reaches
``max_prefix_bases``, then ACCEPT ("timeout").
"""
from __future__ import annotations

import numpy as np

NEG = -(10 ** 9)
KEY_SYMBOLS = 27            # 5 ** 27 < 2 ** 63: one int64 key per suffix


def suffix_array(genome: np.ndarray) -> np.ndarray:
    """Suffix array of ``genome`` (tokens 1..4) followed by a sentinel 0.

    Ranks start from the first ``KEY_SYMBOLS`` symbols of each suffix
    packed into one integer, then double the compared length until every
    rank is distinct."""
    s = np.concatenate([np.asarray(genome, np.int64), [0]])
    n = len(s)
    pad = np.concatenate([s, np.zeros(KEY_SYMBOLS, np.int64)])
    key = np.zeros(n, np.int64)
    for j in range(KEY_SYMBOLS):
        key = key * 5 + pad[j:j + n]
    order = np.argsort(key, kind="stable")
    rank = np.empty(n, np.int64)
    sk = key[order]
    rank[order] = np.concatenate([[0], np.cumsum(sk[1:] != sk[:-1])])
    del key, sk, pad
    k = KEY_SYMBOLS
    while rank[order[-1]] < n - 1:
        second = np.full(n, -1, np.int64)
        second[:n - k] = rank[k:]
        order = np.lexsort((second, rank))
        r, q = rank[order], second[order]
        new = np.concatenate([[0], np.cumsum((r[1:] != r[:-1])
                                             | (q[1:] != q[:-1]))])
        rank[order] = new
        k *= 2
    return order


class ReferenceMapper:
    def __init__(self, genome: np.ndarray, mask: np.ndarray, align: dict,
                 policy: dict):
        self.genome = np.asarray(genome, np.int64)
        self.mask = mask
        self.a = align
        self.p = policy
        seq = np.concatenate([self.genome, [0]])
        self.sa = suffix_array(self.genome)
        bwt = seq[(self.sa - 1) % len(seq)]
        # occ[i, c - 1]: occurrences of base c in bwt[:i]
        self.occ = np.zeros((len(seq) + 1, 4), np.int32)
        for c in range(1, 5):
            np.cumsum(bwt == c, out=self.occ[1:, c - 1])
        hist = np.bincount(seq, minlength=5)
        self.counts = np.concatenate([[0], np.cumsum(hist)[:5]])

    def hits(self, seeds: np.ndarray) -> np.ndarray:
        """(P, k) seeds -> (P, max_hits) genome positions, -1 past the
        range: the first suffixes of each seed's range in suffix order."""
        p, k = seeds.shape
        lo = np.zeros(p, np.int64)
        hi = np.full(p, len(self.occ) - 1, np.int64)
        for i in range(k):
            c = seeds[:, k - 1 - i].astype(np.int64)
            col = (c - 1) % 4            # padding 0 reads the last column
            lo = self.counts[c] + self.occ[lo, col]
            hi = self.counts[c] + self.occ[hi, col]
        offs = np.arange(self.a["max_hits"])
        idx = np.minimum(lo[:, None] + offs, len(self.sa) - 1)
        return np.where(offs < (hi - lo)[:, None], self.sa[idx], -1)

    def candidates(self, windows: np.ndarray) -> np.ndarray:
        a = self.a
        r, L = windows.shape
        offsets = np.arange(0, L - a["seed_len"] + 1, a["seed_stride"])
        seeds = np.stack([windows[:, o:o + a["seed_len"]] for o in offsets],
                         axis=1).reshape(-1, a["seed_len"])
        hits = self.hits(seeds).reshape(r, len(offsets), -1)
        starts = np.where(hits >= 0, hits - offsets[None, :, None], NEG)
        cands = np.full((r, a["max_candidates"]), -1, np.int64)
        for i in range(r):
            vals = starts[i][starts[i] > NEG // 10]
            if len(vals) == 0:
                continue
            keys, votes = np.unique(vals // a["band"], return_counts=True)
            top = keys[np.argsort(-votes)[:a["max_candidates"]]]
            for j, b in enumerate(top):
                pos = int(np.median(vals[vals // a["band"] == b]))
                cands[i, j] = min(max(pos, 0), len(self.genome) - 1)
        return cands

    def _local_scores(self, q: np.ndarray, t: np.ndarray) -> np.ndarray:
        """Best banded local score of each row pair (P, m) x (P, n)."""
        a = self.a
        band = 2 * a["band"]
        p, m = q.shape
        n = t.shape[1]
        row = np.zeros((p, m + 1), np.int64)
        best = np.zeros(p, np.int64)
        i_idx = np.arange(1, m + 1)
        for j in range(n):
            sub = np.where(q == t[:, j:j + 1], a["match"], a["mismatch"])
            in_band = np.abs(i_idx - (j + 1)) <= band
            new = np.zeros((p, m + 1), np.int64)
            for i in range(m):
                v = np.maximum(np.maximum(new[:, i] + a["gap"],
                                          row[:, i + 1] + a["gap"]),
                               row[:, i] + sub[:, i])
                new[:, i + 1] = np.maximum(v, 0) if in_band[i] else 0
            best = np.maximum(best, new.max(axis=1))
            row = new
        return best

    def map(self, windows: np.ndarray) -> dict:
        """Map (R, L) windows of called bases (1..4, zero-filled tails)."""
        a = self.a
        r, L = windows.shape
        c = a["max_candidates"]
        cands = self.candidates(np.asarray(windows, np.int64)) \
            if r else np.zeros((0, c), np.int64)
        wlen = L + 2 * a["band"]
        gpad = np.concatenate([np.zeros(a["band"], np.int64), self.genome,
                               np.zeros(wlen, np.int64)])
        idx = np.clip(cands, 0, None)[..., None] + np.arange(wlen)
        targets = gpad[idx].reshape(r * c, wlen)
        queries = np.repeat(windows.astype(np.int64), c, axis=0)
        scores = self._local_scores(queries, targets).reshape(r, c)
        scores = np.where(cands >= 0, scores, NEG)
        best = np.argmax(scores, axis=1)
        best_score = scores[np.arange(r), best]
        second = np.sort(scores, axis=1)[:, -2] if c > 1 else np.zeros(r)
        mapq = np.clip(best_score - second, 0, 60)
        mapped = best_score > a["min_score_frac"] * a["match"] * L
        pos = np.where(mapped, cands[np.arange(r), best], -1)
        on_target = mapped & self.mask[np.clip(pos, 0, len(self.mask) - 1)]
        return {"mapped": mapped, "on_target": on_target, "mapq": mapq,
                "positions": pos}

    def decide(self, windows: np.ndarray, prefix_lens: np.ndarray):
        """Decision and reason per window: ("accept" | "eject" | "wait",
        "mapped" | "timeout" | "")."""
        p = self.p
        res = self.map(windows)
        out = []
        for i in range(len(windows)):
            if res["mapped"][i] and res["on_target"][i]:
                out.append(("accept", "mapped", int(res["positions"][i])))
            elif (res["mapped"][i]
                  and res["mapq"][i] >= p["min_mapq"]):
                out.append(("eject", "mapped", int(res["positions"][i])))
            elif prefix_lens[i] >= p["max_prefix_bases"]:
                out.append(("accept", "timeout", int(res["positions"][i])))
            else:
                out.append(("wait", "", -1))
        return out
