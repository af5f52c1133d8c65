"""Production mesh definition.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run must
set XLA_FLAGS before the first jax initialisation.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") —
the "pod" axis extends the batch/FSDP dimension across the DCN/ICI
boundary; "model" stays inside a pod (tensor/expert shards never cross
pods).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes: jax 0.9 defaults to
    ``Explicit``, under which the sharding rules' ``with_sharding_constraint``
    calls are rejected.  ``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke tests (same axis names as production,
    sizes 1 — every sharding rule degenerates to replication)."""
    return make_mesh((1, 1), ("data", "model"))


def mesh_name(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)
