"""Mesh construction for single-pod and multi-pod deployments.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS *before* any jax
initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist, as a (data, model) mesh for tests/examples."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
