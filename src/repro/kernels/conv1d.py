"""Basecaller conv1d as an MXU GEMM — the paper's C1xC2 co-design point.

The SoC picks a *pure-CNN* basecaller precisely so that the MAT systolic
array can execute it as dense matrix math.  The TPU-native version of that
decision: lower conv1d onto the MXU as K accumulated GEMMs, performing the
im2col *inside* the kernel with shifted VMEM slices so HBM traffic stays
O(input) (no materialized im2col buffer).

Blocking:
  grid = (B, T_out/bt, C_out/bn); each step loads the input rows
  [i*bt*stride, i*bt*stride + (bt-1)*stride + K) as a main block plus its
  right neighbour (halo), and the full (K, Cin, bn) weight slab.  Both
  blocks are staged in one 32-bit VMEM scratch and each of the K taps is a
  strided ref load from it.  For the paper's basecaller (Cin <= 512,
  K <= 11) the slab is < 3 MB of VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.matmul import _ACTIVATIONS


def stream_carry_len(ksize: int, stride: int) -> int:
    """Input rows carried across chunk boundaries for streaming conv1d.

    With a carry of exactly ``K - stride`` rows prepended to each chunk, a
    'valid' conv over ``[carry, chunk]`` emits exactly ``T/stride`` frames
    per chunk of ``T`` rows (T a multiple of stride) and the next carry is
    always the trailing ``K - stride`` rows — a fixed-shape state, which is
    what lets hundreds of channel sessions batch into one array.  A
    zero-initialized carry makes the whole stream equivalent to a single
    conv with ``K - stride`` rows of left padding ("stream" padding).
    """
    if ksize < stride:
        raise ValueError(f"streaming conv requires K >= stride ({ksize} < {stride})")
    return ksize - stride


def _conv1d_kernel(x_ref, xn_ref, w_ref, bias_ref, o_ref, buf_ref, *,
                   ksize: int, stride: int, activation: str, block_t: int,
                   acc_dtype):
    # x_ref:   (1, block_t*stride, Cin)  rows starting at i*block_t*stride
    # xn_ref:  (1, block_t*stride, Cin)  the next block (halo source)
    # buf_ref: (2*block_t*stride, Cin)   32-bit VMEM staging of both blocks,
    #          so each tap is a strided *ref* load (Mosaic has no strided
    #          value slice, and strided loads only of 32-bit data)
    span = x_ref.shape[1]
    buf_ref[:span, :] = x_ref[0].astype(buf_ref.dtype)
    buf_ref[span:, :] = xn_ref[0].astype(buf_ref.dtype)
    acc = None
    for k in range(ksize):
        # rows k, k+stride, ..., k+(block_t-1)*stride
        xk = buf_ref[pl.ds(k, block_t, stride=stride), :].astype(x_ref.dtype)
        part = jnp.dot(xk, w_ref[k], preferred_element_type=acc_dtype)
        acc = part if acc is None else acc + part
    if bias_ref is not None:
        acc = acc + bias_ref[...].astype(acc.dtype)
    acc = _ACTIVATIONS[activation](acc)
    o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "block_t", "block_n", "activation", "out_dtype",
                     "interpret"),
)
def conv1d(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    stride: int = 1,
    block_t: int = 256,
    block_n: int = 128,
    activation: str = "none",
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """'valid' conv1d.  x: (B, T, Cin), w: (K, Cin, Cout) -> (B, T_out, Cout).

    Requires T_out % block_t == 0 and Cout % block_n == 0 (ops.py pads).
    """
    bsz, t, cin = x.shape
    ksize, _, cout = w.shape
    t_out = (t - ksize) // stride + 1
    block_t = min(block_t, t_out)
    block_n = min(block_n, cout)
    assert t_out % block_t == 0 and cout % block_n == 0, (t_out, block_t, cout, block_n)
    # int8 operands take the fixed-point MAC path: int32 accumulation,
    # exactly like matmul.py (the SoC's int8->int32 MACs)
    int_inputs = jnp.issubdtype(x.dtype, jnp.integer)
    acc_dtype = jnp.int32 if int_inputs else jnp.float32
    if out_dtype is None:
        out_dtype = jnp.int32 if int_inputs else x.dtype
    n_tb = t_out // block_t
    span = block_t * stride  # rows consumed per output block (sans halo)
    # main + neighbour blocks must tile the input: pad T up to (n_tb+1)*span
    t_need = (n_tb + 1) * span
    if x.shape[1] < t_need:
        x = jnp.pad(x, ((0, 0), (0, t_need - x.shape[1]), (0, 0)))

    in_specs = [
        pl.BlockSpec((1, span, cin), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, span, cin), lambda b, i, j: (b, i + 1, 0)),
        pl.BlockSpec((ksize, cin, block_n), lambda b, i, j: (0, 0, j)),
    ]
    operands = [x, x, w]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda b, i, j: (0, j)))
        operands.append(bias.reshape(1, cout))
        kernel = functools.partial(_conv1d_kernel, ksize=ksize, stride=stride,
                                   activation=activation, block_t=block_t,
                                   acc_dtype=acc_dtype)
    else:
        def kernel(x_ref, xn_ref, w_ref, o_ref, buf_ref):
            _conv1d_kernel(x_ref, xn_ref, w_ref, None, o_ref, buf_ref,
                           ksize=ksize, stride=stride, activation=activation,
                           block_t=block_t, acc_dtype=acc_dtype)

    return pl.pallas_call(
        kernel,
        grid=(bsz, n_tb, cout // block_n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_t, block_n), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, t_out, cout), out_dtype),
        scratch_shapes=[pltpu.VMEM((2 * span, cin), jnp.int32 if int_inputs
                                   else jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(*operands)
