"""Production mesh construction (spec'd by the assignment).

A FUNCTION, not a module constant — importing this module never touches
jax device state, so tests/benches see 1 CPU device unless the dry-run
entrypoint has set ``xla_force_host_platform_device_count`` first.
"""

from __future__ import annotations

import jax

from repro.dist.mesh import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, *, multi_pod: bool = False):
    """Small mesh for CI-scale dry-run smoke tests (e.g. 8 host devices)."""
    n = n_devices or len(jax.devices())
    if multi_pod:
        assert n % 2 == 0 and n >= 8
        return auto_mesh((2, 2, n // 4), ("pod", "data", "model"))
    return auto_mesh((2, n // 2), ("data", "model"))
