"""Production mesh construction.

Never touches jax device state at import time — everything is a
function, and the dry-run entry point is the only place that sets
``xla_force_host_platform_device_count``.  All mesh construction goes
through ``repro.compat``, the one module that touches JAX's sharding
API.
"""
from __future__ import annotations

from .. import compat


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic re-mesh)."""
    return compat.make_mesh(tuple(shape), tuple(axes))
