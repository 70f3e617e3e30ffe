"""Production mesh definitions.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state.  The dry-run driver sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
smoke tests and benches see the real single CPU device.

Topology mapping (DESIGN.md §2): `pod` = RSU/cloud layer (cross-pod DCI),
`data` = traffic agents within an RSU (ICI), `model` = tensor parallel.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis explicitly ``Auto``."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def shard_map(f, mesh, in_specs, out_specs, axis_names, check: bool = False):
    """``jax.shard_map``, manual over ``axis_names`` (the rest stay auto);
    ``check`` is its ``check_vma``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=frozenset(axis_names), check_vma=check)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count
    to cover prod(shape) devices)."""
    return make_mesh(shape, axes)


def agent_axes(mesh) -> tuple:
    """Mesh axes along which federated agents are laid out."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_agents(mesh) -> int:
    from math import prod
    return prod(mesh.shape[a] for a in agent_axes(mesh))


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
