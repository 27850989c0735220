"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips of TPU v5e.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across 2 pods; the "pod"
axis carries data parallelism whose collectives cross the inter-pod link
(the gradient-compression and overlap knobs target exactly that axis).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]
              ) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests, elastic re-mesh, smoke runs)."""
    return _make_mesh(shape, axes)
