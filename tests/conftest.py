"""Test config: single CPU device (the dry-run sets its own device count
in a subprocess), moderate hypothesis budgets."""

import jax
import pytest
from hypothesis import HealthCheck, settings

settings.register_profile(
    "ci",
    max_examples=25,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
)
settings.load_profile("ci")

jax.config.update("jax_platform_name", "cpu")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running end-to-end test")
    config.addinivalue_line(
        "markers",
        "kernel_parity: registry-generated kernel oracle cross-checks "
        "(CI kernel-parity job runs `pytest -m kernel_parity`)",
    )
    config.addinivalue_line(
        "markers",
        "grad_parity: sparsity-aware backward (custom_vjp) gradient "
        "cross-checks vs the dense ref gradient "
        "(CI grad-parity job runs `pytest -m grad_parity`)",
    )
    config.addinivalue_line(
        "markers",
        "serving: continuous-batching engine parity/property/KV-roundtrip "
        "suite (CI serving job runs `pytest -m serving`)",
    )
    config.addinivalue_line(
        "markers",
        "spec: RunSpec round-trip/parity/coverage suite "
        "(CI spec job runs `pytest -m spec`)",
    )
    config.addinivalue_line(
        "markers",
        "telemetry: spring-trace metrics/span/latency-attribution suite "
        "(CI telemetry job runs `pytest -m telemetry`)",
    )
    config.addinivalue_line(
        "markers",
        "paging: spring-pages paged/COW KV pool parity + property suite "
        "(CI paging job runs `pytest -m paging`)",
    )
    config.addinivalue_line(
        "markers",
        "elastic: spring-survive chaos/snapshot/shed suite "
        "(CI elastic job runs `pytest -m elastic`)",
    )
    config.addinivalue_line(
        "markers",
        "mesh: spring-mesh packed-collective + sharded-oracle parity suite "
        "(CI mesh job runs `pytest -m mesh` under "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8; "
        "device-gated tests self-skip on a 1-device host)",
    )


@pytest.fixture
def debug_mesh():
    """An explicit pod1.data4.model1 mesh over 8 host devices; skips when
    the pool is too small (tier-1 runs single-device — the CI mesh job
    sets the XLA flag before jax initializes)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    from repro.dist.mesh import make_explicit_mesh

    return make_explicit_mesh(1, 4, 1)


@pytest.fixture(autouse=True)
def _isolate_metrics():
    """Snapshot/restore the default MetricsRegistry around every test.

    The registry now backs the kernel dispatch counters (global mutable
    state by design — it outlives any one run), so without isolation a
    test's asserts would see whatever counts earlier tests dispatched.
    """
    from repro.telemetry import default_registry

    reg = default_registry()
    saved = reg.snapshot()
    try:
        yield reg
    finally:
        reg.reset()
        reg.restore(saved)
