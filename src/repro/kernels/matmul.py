"""MAT: the paper's systolic matrix engine as a Pallas TPU matmul kernel.

The SoC in the paper pairs a 4x4 weight-stationary systolic array ("MAT")
with RISC-V cores; its co-design insight is that a pure-CNN basecaller can be
expressed entirely as dense matrix math so the systolic array does all heavy
lifting.  On TPU the MXU *is* a 128x128 systolic array, so the faithful
adaptation is a tiled GEMM whose BlockSpecs keep the working set in VMEM and
whose tile shapes are MXU-aligned (multiples of 128 in the lane dimension).

Design notes (VMEM budget, v5e ~16MB usable):
  * grid = (M/bm, N/bn, K/bk); K is the innermost (sequential) grid axis so
    the f32 accumulator scratch lives across K steps.
  * per-step VMEM: bm*bk (A) + bk*bn (B) + bm*bn (acc f32) + bm*bn (out)
    with double buffering on A/B.  Default (256, 256, 512) bf16:
    2*(256*512 + 512*256)*2B + 256*256*4B + 256*256*2B ~= 1.4 MB.
  * epilogue (bias add + activation) is fused into the final K step, exactly
    like the paper fuses ReLU into the MAT drain phase.
  * int8 x int8 -> int32 accumulation mirrors the SoC's fixed-point MACs and
    is exposed for the quantized basecaller path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_ACTIVATIONS = {
    "none": lambda x: x,
    "relu": jax.nn.relu,
    # nemotron-style squared ReLU: relu(x)**2
    "squared_relu": lambda x: jnp.square(jax.nn.relu(x)),
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
}


def _matmul_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *, activation: str,
                   n_k: int, acc_dtype):
    """One (bm, bn) output tile; grid axis 2 walks the K dimension."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=acc_dtype)

    @pl.when(k_step == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        if bias_ref is not None:
            acc = acc + bias_ref[...].astype(acc.dtype)
        acc = _ACTIVATIONS[activation](acc)
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m", "block_n", "block_k", "activation", "out_dtype", "interpret",
    ),
)
def matmul(
    a: jax.Array,
    b: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    activation: str = "none",
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """``activation(a @ b + bias)`` with MXU-tiled Pallas.

    a: (M, K), b: (K, N), bias: (N,) or None.  M/N/K need not be multiples of
    the block sizes; the wrapper in ops.py pads (this entry requires aligned
    shapes and is the raw kernel).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        "matmul() requires block-aligned shapes; use ops.mat_mul for padding"
    )
    int_inputs = jnp.issubdtype(a.dtype, jnp.integer)
    acc_dtype = jnp.int32 if int_inputs else jnp.float32
    if out_dtype is None:
        out_dtype = jnp.int32 if int_inputs else a.dtype
    n_k = k // block_k

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, l: (i, l)),
        pl.BlockSpec((block_k, block_n), lambda i, j, l: (l, j)),
    ]
    operands = [a, b]
    if bias is not None:
        assert bias.shape == (n,), bias.shape
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, l: (0, j)))
        operands.append(bias.reshape(1, n))

    kernel = functools.partial(
        _matmul_kernel if bias is not None else _matmul_nobias_kernel,
        activation=activation,
        n_k=n_k,
        acc_dtype=acc_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)


def _matmul_nobias_kernel(a_ref, b_ref, o_ref, acc_ref, *, activation: str,
                          n_k: int, acc_dtype):
    _matmul_kernel(a_ref, b_ref, None, o_ref, acc_ref, activation=activation,
                   n_k=n_k, acc_dtype=acc_dtype)
