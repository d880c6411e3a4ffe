"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.  Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model") — the "pod" axis
is the slowest (DCN) dimension and only ever carries batch-dim (data
parallel) traffic plus the gradient all-reduce.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape))


def make_local_mesh(n_devices: int | None = None, model: int = 1):
    """Small mesh over the actually-available devices (tests, examples)."""
    n = n_devices or len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         (AxisType.Auto,) * 2)
