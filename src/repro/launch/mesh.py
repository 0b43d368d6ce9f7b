"""Production meshes for the multi-pod dry-run.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds meshes.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e-256 topology).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_debug_mesh(n_devices: int | None = None, model: int = 2) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests)."""
    n = n_devices or len(jax.devices())
    model = min(model, n)
    return jax.make_mesh(
        (n // model, model), ("data", "model"), axis_types=_auto(2)
    )


def _auto(n_axes: int) -> tuple:
    """Auto axis types: the models place their tensors through sharding
    constraints (``MeshRules.constrain``), not explicit-axis typing, which
    ``jax.make_mesh`` defaults to."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def dp_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Axes that shard the batch (everything except the tensor axis)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def mesh_summary(mesh: jax.sharding.Mesh) -> str:
    return "x".join(
        f"{name}={size}" for name, size in zip(mesh.axis_names, mesh.devices.shape)
    )
