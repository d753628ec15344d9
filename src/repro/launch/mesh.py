"""Production mesh definitions.

A function, not a module-level constant: importing this module never touches
jax device state.  Single-pod: 16x16 = 256 chips (data, model); multi-pod:
2x16x16 = 512 chips with a pure-DP 'pod' outer axis (gradient all-reduce
crosses pods once per step over DCN; TP/EP collectives stay inside a pod's
ICI — how v5e pods actually compose)."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests, smoke runs)."""
    return _make_mesh((data, model), ("data", "model"))
