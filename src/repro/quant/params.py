"""Calibrate once, quantize weights once: the ``QuantizedParams`` path.

The PR-3 precision policy re-derived scales and re-rounded the *static*
weights on every matmul call — wasted work on every dispatch and no story
for conv1d.  This module is the quantize-once replacement:

    calib  = quant.calibrate(feed, observer="percentile")      # optional
    qparams = quant.quantize_params(params, calib)             # once
    logits = basecaller.apply(qparams, signal, cfg)            # every call

``quantize_params`` walks a parameter pytree and replaces weight leaves
(by key name — matmul/conv operands only, never embeddings, norms or
depthwise filters) with :class:`~repro.quant.core.QuantizedTensor`:
per-channel symmetric int8 along the output-feature axis, scales stored
next to the payload.  Everything downstream — ``ops.conv1d``,
``ops.mat_mul``, the model layers — recognizes the container and takes
the fabric's int8 MAC path with **no per-call weight re-quantization**
(counted: ``fabric.precision.<op>.int8`` hits with zero
``fabric.precision.<op>.weight_requant``).

A :class:`Calibration` (from :func:`calibrate`) additionally pins each
op's input-activation scale so serving does not even compute a dynamic
activation absmax.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.quant.core import QuantizedTensor, is_quantized, quantize_tensor
from repro.quant.observers import make_observer

# Weight-leaf key names eligible for int8 by default: exactly the operands
# of fabric matmul/conv ops.  Embeddings (table lookups), norm scales and
# depthwise conv filters (elementwise) never meet an int8 MAC.
DEFAULT_WEIGHT_KEYS = frozenset({
    "w",                       # basecaller / variant-caller conv weights
    "wi", "wi_gate", "wo",     # MLP
    "wq", "wk", "wv",          # attention projections
    "in_proj", "out_proj",     # mamba2 projections
})


@dataclasses.dataclass(frozen=True)
class Calibration:
    """Per-op input-activation scales, keyed by the op's scope name
    (e.g. ``"conv1"`` for basecaller params ``{"conv1": {"w": ...}}``)."""
    act_scales: Mapping[str, np.ndarray]

    def act_scale(self, scope: str):
        return self.act_scales.get(scope)


def calibrate(feed: Iterable, *, observer: str = "minmax",
              **observer_kwargs) -> Calibration:
    """Fold streaming ``(scope, activation)`` pairs into per-scope scales.

    ``feed`` yields ``(scope_name, array)`` pairs — e.g.
    :func:`repro.core.basecaller.layer_inputs` over a stream of signal
    chunks.  One observer per scope; returns the scales they settle on.
    """
    obs: dict = {}
    for scope, x in feed:
        if scope not in obs:
            obs[scope] = make_observer(observer, **observer_kwargs)
        obs[scope].update(x)
    return Calibration({k: o.scale() for k, o in obs.items()})


def _key_name(entry) -> str:
    """Key path entry -> plain string ('conv1', 'w', ...)."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def select_weight_leaf(names, leaf, weight_keys=DEFAULT_WEIGHT_KEYS) -> bool:
    """The one weight-leaf selection rule, shared by :func:`quantize_params`
    and QAT's ``fake_quant_params`` — so training always fake-quantizes
    exactly the leaf set serving stores as int8."""
    return bool(names and names[-1] in weight_keys
                and hasattr(leaf, "ndim") and leaf.ndim >= 2
                and not is_quantized(leaf))


def quantize_params(params, calib: Optional[Calibration] = None, *,
                    weight_keys: frozenset = DEFAULT_WEIGHT_KEYS,
                    per_channel: bool = True,
                    predicate: Optional[Callable] = None,
                    stack_dims: int = 0):
    """Replace weight leaves with int8 :class:`QuantizedTensor`s, once.

    ``calib``        optional :class:`Calibration`; a leaf under scope
                     ``foo`` picks up ``calib.act_scale("foo")`` as its
                     static input-activation scale.
    ``weight_keys``  leaf key names to quantize (see DEFAULT_WEIGHT_KEYS).
    ``per_channel``  one scale per output channel (last axis) vs per-tensor.
    ``predicate``    optional ``f(path_names, leaf) -> bool`` overriding the
                     key-name rule entirely.
    ``stack_dims``   leading *stack* dims on every weight (the transformer's
                     num_blocks dim): per-channel scales are computed per
                     stack entry and stored ``(*stack, C)`` with ``axis=-1``
                     so the params scan block-wise under ``lax.scan``.

    Biases and every other leaf pass through unchanged; the result is a
    pytree of the same structure, usable anywhere the float params were.
    """
    # already-quantized leaves are opaque (idempotent pass-through), not
    # pytrees to descend into
    flat, treedef = jax.tree.flatten_with_path(params, is_leaf=is_quantized)
    out = []
    for path, leaf in flat:
        names = [_key_name(p) for p in path]
        if predicate is not None:
            # already-quantized leaves stay pass-through (idempotence) even
            # under a permissive custom predicate
            take = predicate(names, leaf) and not is_quantized(leaf)
        else:
            take = select_weight_leaf(names, leaf, weight_keys)
        if not take:
            out.append(leaf)
            continue
        act_scale = None
        if calib is not None:
            scope = names[-2] if len(names) >= 2 else names[-1]
            act_scale = calib.act_scale(scope)
        axis = leaf.ndim - 1 if per_channel else None
        out.append(quantize_tensor(leaf, axis=axis, act_scale=act_scale,
                                   stack_dims=stack_dims))
    return jax.tree_util.tree_unflatten(treedef, out)


def dequantize_params(params):
    """Inverse convenience: QuantizedTensor leaves -> float32 arrays."""
    return jax.tree_util.tree_map(
        lambda x: x.dequantize() if is_quantized(x) else x, params,
        is_leaf=is_quantized)


def params_precision(params) -> str:
    """The MAC datapath a parameter pytree implies: ``"int8"`` when any
    weight is a stored :class:`QuantizedTensor`, else ``"bf16"`` when the
    floating leaves are bfloat16, else ``"fp32"`` (energy accounting)."""
    leaves = jax.tree_util.tree_leaves(params, is_leaf=is_quantized)
    if any(is_quantized(x) for x in leaves):
        return "int8"
    if any(getattr(x, "dtype", None) == jnp.bfloat16 for x in leaves):
        return "bf16"
    return "fp32"


def quantized_fraction(params) -> float:
    """Fraction of parameter scalars stored as int8 (reporting helper)."""
    total = q = 0
    for _, leaf in jax.tree.flatten_with_path(params, is_leaf=is_quantized)[0]:
        n = int(np.prod(leaf.shape))
        total += n
        if is_quantized(leaf):
            q += n
    return q / max(total, 1)
