"""Mamba-2 SSD (state-space duality) chunked scan as a Pallas kernel.

SSD's insight is the same co-design move the paper makes for its basecaller:
restructure a recurrent computation so a matrix engine does the work.  The
sequence is split into chunks; within a chunk the recurrence is unrolled into
dense matmuls (MXU food), and only a small (d_state x d_head) state crosses
chunk boundaries — which maps onto a sequential Pallas grid axis carrying the
state in VMEM scratch.

Per (head, chunk) step with chunk length Lc, head dim dh, state dim ds:
  cum_t   = cumsum(log a)                          (Lc,)
  L[t,s]  = exp(cum_t - cum_s) for s <= t else 0   (Lc, Lc)
  Y_intra = ((C B^T) * L) X                        two (Lc,Lc)x(Lc,*) GEMMs
  Y_inter = (C * exp(cum)) S_prev                  (Lc,ds)x(ds,dh)
  S_new   = exp(cum_last) S_prev
          + (B * exp(cum_last - cum))^T X          (ds,Lc)x(Lc,dh)

VMEM: X/B/C blocks + (Lc, Lc) decay matrix + (ds, dh) state; Lc=256,
dh=64, ds=128 -> ~0.6 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, s_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0].astype(jnp.float32)          # (Lc, dh)
    la = a_ref[0].astype(jnp.float32)         # (1, Lc) log decay
    b = b_ref[0].astype(jnp.float32)          # (Lc, ds)
    c = c_ref[0].astype(jnp.float32)          # (Lc, ds)

    cum = jnp.cumsum(la[0])                   # (Lc,)
    # intra-chunk: masked decay matrix
    seg = cum[:, None] - cum[None, :]         # cum_t - cum_s
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(cols <= rows, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y = jnp.dot(cb * decay, x, preferred_element_type=jnp.float32)
    # inter-chunk: contribution of carried state
    y += jnp.dot(c * jnp.exp(cum)[:, None], s_ref[...],
                 preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)
    # state update
    total = cum[-1]
    w = jnp.exp(total - cum)[:, None]         # (Lc, 1)
    s_ref[...] = jnp.exp(total) * s_ref[...] + jax.lax.dot_general(
        b * w, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,
    log_a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """x: (BH, T, dh), log_a: (BH, T), b/c: (BH, T, ds) -> y: (BH, T, dh).

    T must be a multiple of ``chunk`` (ops.py pads).  log_a must be <= 0
    (decay), as produced by -softplus parameterizations.
    """
    bh, t, dh = x.shape
    ds = b.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk
    la = log_a.reshape(bh, t, 1).transpose(0, 2, 1)  # (BH, 1, T): lane-major

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dh), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, 1, chunk), lambda h, i: (h, 0, i)),
            pl.BlockSpec((1, chunk, ds), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, ds), lambda h, i: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dh), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, dh), x.dtype),
        scratch_shapes=[pltpu.VMEM((ds, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, la, b, c)
