"""Mesh construction for the production pod configurations.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; tests and benches see the real single device.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (16,16) ("data","model") = 256 chips.
    Multi-pod:   (2,16,16) ("pod","data","model") = 512 chips; the "pod"
    axis is data-parallel by default (lowest-bandwidth axis gets the
    lowest-frequency collective: one gradient all-reduce per step).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """Arbitrary mesh helper (tests, pipeline-parallel experiments)."""
    return _mesh(shape, axes)


def mesh_for(placement) -> jax.sharding.Mesh:
    """Mesh for a ``repro.bench.spec.Placement`` (duck-typed: anything
    with ``mesh_shape``/``mesh_axes``) — the bench runner's bridge from
    a declarative placement to a live device mesh."""
    return _mesh(placement.mesh_shape, placement.mesh_axes)


def dp_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (everything except "model")."""
    return tuple(a for a in mesh.axis_names if a != "model")


def axis_size(mesh: jax.sharding.Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
