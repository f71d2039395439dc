"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (device count is locked at first jax init, and smoke tests
must see 1 CPU device while the dry-run sees 512 forced host devices).
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: 'data' = batch/FSDP axis, 'model' = TP/EP/shard axis, 'pod' =
    cross-pod data parallelism (or extra corpus shards for LANNS serving).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 4, *, pod: int = 0):
    """Small mesh for CI-scale dry-run tests (requires forced host devices)."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))
