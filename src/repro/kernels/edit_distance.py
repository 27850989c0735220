"""ED: the paper's edit-distance engine as an anti-diagonal wavefront kernel.

The SoC's ED block is a *string-independent PE array*: one PE per cell of the
current anti-diagonal of the DP matrix, all firing in lock-step.  The TPU
adaptation assigns the anti-diagonal to the *sublane* dimension of the VPU
and a block of independent sequence pairs to the *lane* dimension, so a
single VPU issue updates (m+1) x 128 DP cells — the 8x128 vector unit plays
the role of the PE array, and the wavefront steps become a fori_loop whose
state (three rotating diagonal buffers) never leaves VMEM.

Two entry points share the machinery:
  * ``levenshtein``   — unit-cost edit distance (the ED block's function).
  * ``banded_align``  — banded Needleman-Wunsch / Smith-Waterman scores with
    match/mismatch/gap parameters (the seed-extension workload of Section
    II-B.2); banding is a wavefront mask.

VMEM budget per (m, n, block_p=128) tile, i32 buffers:
  3 diagonal buffers (m+1, 128) + query (m, 128) + target (n, 128)
  = (5m + 2n) * 512 B;  m = n = 1024 -> ~3.6 MB, comfortably in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BIG = 2**20


def _wavefront_kernel(q_ref, t_ref, o_ref, prev2_ref, prev_ref, tdiag_ref,
                      best_ref, *, m: int, n: int, local: bool, band: int,
                      match: int, mismatch: int, gap: int):
    """Shared wavefront body.

    Minimization (edit distance) is expressed as maximization of negated
    scores so one code path serves both:  levenshtein == match=0,
    mismatch=-1, gap=-1, band=inf, local=False, and distance = -score.
    """
    bp = q_ref.shape[1]
    neg = jnp.int32(-_BIG)
    rows = jax.lax.broadcasted_iota(jnp.int32, (m + 1, bp), 0)  # i index

    # t = 0 diagonal: D[0,0]
    prev_ref[...] = jnp.where(rows == 0, 0, neg)
    prev2_ref[...] = jnp.full((m + 1, bp), neg)
    tdiag_ref[...] = jnp.zeros((m + 1, bp), q_ref.dtype)
    best_ref[...] = jnp.zeros((1, bp), jnp.int32)

    def step(t, _):
        prev = prev_ref[...]
        prev2 = prev2_ref[...]
        # shift target chars down the diagonal; row 0 takes target[t-1]
        t_new = t_ref[pl.ds(t - 1, 1), :]
        tdiag = jnp.concatenate([t_new, tdiag_ref[: m]], axis=0)
        tdiag_ref[...] = tdiag

        prev_shift = jnp.concatenate(
            [jnp.full((1, bp), neg), prev[: m]], axis=0)
        prev2_shift = jnp.concatenate(
            [jnp.full((1, bp), neg), prev2[: m]], axis=0)
        qdiag = jnp.concatenate([jnp.zeros((1, bp), q_ref.dtype), q_ref[...]],
                                axis=0)
        sub = jnp.where(qdiag == tdiag, jnp.int32(match), jnp.int32(mismatch))

        new = jnp.maximum(
            jnp.maximum(prev_shift + gap, prev + gap),  # del / ins
            prev2_shift + sub,                          # substitution
        )
        # DP boundary rows: D[0, t] and D[t, 0] are *set* (not maxed): the
        # recurrence at the wavefront edge reads out-of-matrix cells whose
        # floor value (0 in local mode) would otherwise seed phantom
        # alignment starts before the sequences begin.
        edge0 = jnp.int32(0) if local else jnp.int32(gap) * t
        new = jnp.where(rows == 0, edge0, new)
        new = jnp.where(rows == t, edge0, new)
        # wavefront validity: 0 <= j = t - i <= n, and |i - j| <= band
        j = t - rows
        valid = (j >= 0) & (j <= n)
        if band >= 0:
            valid &= jnp.abs(rows - j) <= band
        floor = jnp.int32(0) if local else neg
        new = jnp.where(valid, new, floor)
        if local:
            new = jnp.maximum(new, 0)
            best_ref[...] = jnp.maximum(best_ref[...],
                                        jnp.max(new, axis=0, keepdims=True))
        prev2_ref[...] = prev
        prev_ref[...] = new
        return 0

    jax.lax.fori_loop(1, m + n + 1, step, 0)
    if local:
        o_ref[...] = best_ref[...]
    else:
        o_ref[...] = prev_ref[m:m + 1, :]


def _wavefront(query, target, *, local, band, match, mismatch, gap, block_p,
               interpret):
    """query: (P, m), target: (P, n) token arrays -> (P,) i32 scores."""
    p, m = query.shape
    _, n = target.shape
    assert p % block_p == 0, (p, block_p)
    qt = query.T.astype(jnp.int32)  # (m, P): pairs on lanes
    tt = target.T.astype(jnp.int32)

    kernel = functools.partial(
        _wavefront_kernel, m=m, n=n, local=local, band=band, match=match,
        mismatch=mismatch, gap=gap)
    out = pl.pallas_call(
        kernel,
        grid=(p // block_p,),
        in_specs=[
            pl.BlockSpec((m, block_p), lambda i: (0, i)),
            pl.BlockSpec((n, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((m + 1, block_p), jnp.int32),
            pltpu.VMEM((m + 1, block_p), jnp.int32),
            pltpu.VMEM((m + 1, block_p), jnp.int32),
            pltpu.VMEM((1, block_p), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(qt, tt)
    return out[0]


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def levenshtein(query: jax.Array, target: jax.Array, *, block_p: int = 128,
                interpret: bool = False) -> jax.Array:
    """Batched unit-cost edit distance — the ED engine's native op.

    query: (P, m), target: (P, n) integer token arrays (pad with distinct
    sentinels if lengths vary); returns (P,) int32 distances.
    """
    score = _wavefront(query, target, local=False, band=-1, match=0,
                       mismatch=-1, gap=-1, block_p=block_p,
                       interpret=interpret)
    return -score


@functools.partial(
    jax.jit,
    static_argnames=("band", "match", "mismatch", "gap", "local", "block_p",
                     "interpret"),
)
def banded_align(query: jax.Array, target: jax.Array, *, band: int,
                 match: int = 2, mismatch: int = -4, gap: int = -2,
                 local: bool = False, block_p: int = 128,
                 interpret: bool = False) -> jax.Array:
    """Banded NW (global) / SW (local) alignment scores for seed extension."""
    return _wavefront(query, target, local=local, band=band, match=match,
                      mismatch=mismatch, gap=gap, block_p=block_p,
                      interpret=interpret)
