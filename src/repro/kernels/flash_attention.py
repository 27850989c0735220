"""Blocked online-softmax attention (flash) — TPU target for long contexts.

Not a paper kernel per se, but the assigned-architecture pool (32k prefill,
500k decode contexts) needs O(S) attention memory; this kernel is the TPU
target for the chunked-attention schedule used by the pure-JAX path
(models/attention.py:chunked_attention), against which it is verified.

Blocking: grid = (B*Hq, Sq/bq, Skv/bk); the KV axis is the sequential grid
axis carrying the online-softmax state (m, l, acc) in VMEM scratch.  Causal
blocks strictly above the diagonal are skipped with pl.when (the classic
flash-2 schedule).  GQA is handled by pointing the K/V index_map at
q_head // group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30
_STATS = 128  # stat buffers keep a full lane dim; column 0 is authoritative


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  sq: int, skv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    # last kv block this q block needs (causal: blocks past the diagonal skip)
    offs = skv - sq  # causal alignment for decode-style Sq < Skv
    last_k = jnp.minimum(
        n_k - 1,
        (qi * block_q + block_q - 1 + offs) // block_k) if causal else n_k - 1

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki <= last_k)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rq = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            ck = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(ck <= rq + offs, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == last_k)
    def _epilogue():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D); Hq % Hkv == 0."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = float(d) ** -0.5 if scale is None else scale
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    def kv_map(h, i, j):
        return ((h // hq) * hkv + (h % hq) // group, j, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, sq=sq, skv=skv)
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, sq // block_q, skv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _STATS), jnp.float32),
            pltpu.VMEM((block_q, _STATS), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
