"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the ``pod``
axis is pure data parallelism (per-step gradient / metric reductions are
the only cross-pod collectives; DCN-friendly).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the partition rules steer XLA with sharding hints, which
    # Explicit axes (jax.make_mesh's default since JAX 0.7) reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the same axis names (tests / CPU smoke)."""
    return _mesh((1, 1), ("data", "model"))
