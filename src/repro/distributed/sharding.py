"""Logical-axis sharding: t5x-style rules without the framework.

Models tag every parameter (via ParamBuilder) and key activations (via
``shard``) with *logical* axis names; this module maps them to mesh axes:

    "batch"  -> ("pod", "data")       # data parallel (pods included)
    "vocab"  -> "model"               # tensor-parallel vocab/embedding
    "heads"  -> "model"               # flattened q/kv projection outputs
    "mlp"    -> "model"               # FFN width
    "expert" -> "data"                # expert parallelism
    "embed"  -> ("pod", "data")|None  # FSDP (ZeRO-3) for large archs

Robustness rules applied when concretizing a PartitionSpec:
  * a dim whose size is not divisible by its mesh-axis extent is left
    unsharded (jax rejects uneven shardings — e.g. 8 KV heads on a 16-wide
    model axis fall back to replication; models flatten head dims into
    feature dims so this rarely triggers),
  * a mesh axis may appear only once per spec; later logical dims lose.

The context is process-global (set by the launcher / trainer); with no
context active every helper is a no-op, so the same model code runs on a
bare CPU test and a 512-chip dry-run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class ShardingContext:
    mesh: Mesh
    rules: dict[str, Any]  # logical name -> mesh axis | tuple | None

    def axis_size(self, mesh_axes) -> int:
        if mesh_axes is None:
            return 1
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        return int(np.prod([self.mesh.shape[a] for a in mesh_axes]))


_CTX: Optional[ShardingContext] = None


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """All batch-parallel axes present in the mesh ('pod' first)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def default_rules(mesh: Mesh, *, fsdp: bool = False,
                  expert_axis: bool = True,
                  overrides: dict[str, Any] | None = None) -> dict[str, Any]:
    d = data_axes(mesh)
    rules: dict[str, Any] = {
        "batch": d,
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "expert": "data" if expert_axis else None,
        "embed": d if fsdp else None,
        "ssm_inner": "model",
        "ssm_heads": "model",
        "ssm_state": None,
        "seq": None,
        "act_embed": None,
        "act_mlp": "model",
        "act_heads": "model",
        "act_seq": None,   # context-parallel attention (heads % model != 0)
        "act_heads_q": None,  # per-head attention sharding (opt mode)
        "moe_cap": "data",  # MoE capacity dim (row-aligned; dedup-dropped under EP)
        "kv_seq": None,
    }
    if overrides:
        rules.update(overrides)
    return rules


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: dict[str, Any]):
    global _CTX
    prev = _CTX
    _CTX = ShardingContext(mesh=mesh, rules=rules)
    try:
        yield _CTX
    finally:
        _CTX = prev


def active() -> Optional[ShardingContext]:
    return _CTX


def extent(logical_name: str) -> int:
    """Mesh extent a logical axis maps to (1 when inactive/unmapped)."""
    ctx = _CTX
    if ctx is None:
        return 1
    return ctx.axis_size(ctx.rules.get(logical_name))


def logical_spec(axes: tuple, shape: tuple | None = None) -> P:
    """Map logical axis names to a PartitionSpec under the active rules."""
    ctx = _CTX
    if ctx is None:
        return P()
    used: set[str] = set()
    entries = []
    for i, name in enumerate(axes):
        mesh_axes = ctx.rules.get(name) if name else None
        if mesh_axes is None:
            entries.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        mesh_axes = tuple(a for a in mesh_axes
                          if a in ctx.mesh.shape and a not in used)
        if not mesh_axes:
            entries.append(None)
            continue
        # NOTE: deliberately not named ``extent`` — that would shadow the
        # module-level extent() helper for the rest of this function
        axes_extent = int(np.prod([ctx.mesh.shape[a] for a in mesh_axes]))
        if shape is not None and shape[i] % axes_extent != 0:
            entries.append(None)
            continue
        used.update(mesh_axes)
        entries.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def shard(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint by logical names (no-op without context)."""
    ctx = _CTX
    if ctx is None:
        return x
    spec = logical_spec(tuple(axes), x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


LANE_AXIS = "data"  # flowcell channel lanes are batch-parallel work


def lane_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D device mesh for lane-parallel streaming (flowcell channels).

    Lanes are plain batch parallelism, so the axis is the standard ``data``
    axis — ``default_rules`` and ``logical_spec("batch")`` apply unchanged.
    ``n_devices=None`` takes every local device; ``n_devices=1`` is the
    single-device degenerate mesh (useful for mesh-invariance tests).
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 0 < n <= len(devs):
        raise ValueError(f"n_devices={n} not in 1..{len(devs)}")
    return Mesh(np.asarray(devs[:n]), (LANE_AXIS,))


def param_shardings(axes_tree, shape_tree):
    """NamedSharding tree for a params pytree (shape_tree from eval_shape)."""
    ctx = _CTX
    assert ctx is not None, "param_shardings requires an active context"

    def one(axes, leaf):
        return NamedSharding(ctx.mesh, logical_spec(axes, leaf.shape))

    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def spec_tree(axes_tree, shape_tree):
    """PartitionSpec tree (for in_shardings= at jit boundaries)."""

    def one(axes, leaf):
        return logical_spec(axes, leaf.shape)

    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple))
