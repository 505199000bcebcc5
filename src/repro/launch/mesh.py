"""Production mesh definitions.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is the DCN dimension (batch sharding + hierarchical gradient
reduction); "data" doubles as the FSDP axis; "model" carries TP/EP/SP.

A function (never a module-level constant) so importing this module never
touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init;
tests and benches see the real single device.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto sharding mode."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small host-device meshes)."""
    return _mesh(tuple(shape), tuple(axes))


def batch_axes(mesh) -> tuple:
    """Axes that shard the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
