"""Explicit ``(pod, data, model)`` mesh construction for spring-mesh.

``MeshSpec`` kinds ("single", "debug", ...) keep resolving through
``api.sessions.build_mesh``; this module handles the explicit-axes form
(``--set shape.mesh.data=4``), where the spec names the extents directly
and the device pool must be large enough to honor them.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def auto_mesh(shape: tuple, axes: tuple) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the logical sharding
    constraints (``runtime/sharding.py``) may only name Auto axes, and
    ``make_mesh`` defaults to Explicit ones."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_explicit_mesh(pod: int, data: int, model: int) -> Mesh:
    """Build a ``(pod, data, model)`` mesh over the first pod*data*model
    visible devices (``jax.make_mesh`` device order, same as the debug
    mesh).  On a CPU host the pool is grown with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the CI mesh
    job and the tests/conftest.py ``debug_mesh`` fixture both do."""
    need = pod * data * model
    have = len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"shape.mesh pod{pod}.data{data}.model{model} needs {need} "
            f"devices but only {have} are visible; on a CPU host export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            "before jax initializes")
    return auto_mesh((pod, data, model), ("pod", "data", "model"))


def data_axis_size(mesh: Mesh) -> int:
    """Extent of the ``data`` axis (1 when the mesh doesn't have one)."""
    return int(dict(mesh.shape).get("data", 1))
