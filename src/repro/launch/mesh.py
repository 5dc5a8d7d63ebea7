"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches JAX device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first JAX
init, and smoke tests must keep seeing 1 device.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
leading ``pod`` axis is pure data parallelism over the DCN (see
repro/parallel/specs.py).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    return make_mesh((data, model), ("data", "model"))
