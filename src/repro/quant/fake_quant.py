"""Fake-quant primitives for quantization-aware training (QAT).

Forward: the exact int8 round-trip the serving path will apply
(quantize -> dequantize with the canonical symmetric scheme).  Backward:
straight-through estimator — the rounding step is treated as identity so
gradients flow to the underlying float weights.  Training against the
quantization noise is what closes most of the PTQ accuracy gap on the
micro basecaller (``train.micro_basecaller(..., qat=True)``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant.core import (absmax, dequantize, is_quantized, quantize,
                              symmetric_scale)
from repro.quant.params import (DEFAULT_WEIGHT_KEYS, _key_name,
                                select_weight_leaf)


def fake_quant(x: jax.Array, *, axis: Optional[int] = None,
               scale=None) -> jax.Array:
    """int8 round-trip with a straight-through gradient.

    ``scale`` pins the scale (QAT with frozen calibration); default derives
    it from the current tensor (per-``axis`` or per-tensor absmax).
    """
    if scale is None:
        scale = symmetric_scale(absmax(x, axis))
    rounded = dequantize(quantize(x, scale, axis=axis), scale, axis=axis)
    rounded = rounded.astype(x.dtype)
    # STE: forward sees the rounded value, backward sees identity
    return x + jax.lax.stop_gradient(rounded - x)


def fake_quant_params(params, *, weight_keys: frozenset = DEFAULT_WEIGHT_KEYS,
                      per_channel: bool = True):
    """Fake-quantize the same weight leaves ``quantize_params`` would
    quantize for real, leaving everything else (biases, norms) untouched —
    so QAT optimizes exactly the deployment numerics."""
    flat, treedef = jax.tree.flatten_with_path(params, is_leaf=is_quantized)
    out = []
    for path, leaf in flat:
        names = [_key_name(p) for p in path]
        if select_weight_leaf(names, leaf, weight_keys):
            axis = leaf.ndim - 1 if per_channel else None
            leaf = fake_quant(leaf, axis=axis)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def fake_quant_activation(x: jax.Array, scale=None) -> jax.Array:
    """Per-tensor activation fake-quant (dynamic scale unless pinned)."""
    return fake_quant(x, axis=None, scale=scale)
