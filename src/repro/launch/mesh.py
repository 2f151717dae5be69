"""Production mesh definitions.

A function, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only the dry-run
entry points' ``main`` set XLA_FLAGS for 512 placeholder devices).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod (v5e); multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests/examples): (1, n) data×model."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"))


def mesh_axis_size(mesh, *names: str) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for name in names:
        n *= sizes.get(name, 1)
    return n
