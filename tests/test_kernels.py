"""Kernel behavioral tests (SR stream properties, tile-skip invariance,
grad-path usability, SSD state handoff).

Oracle parity for every registered (op, impl) pair is NOT enumerated here
any more: ``tests/test_kernel_registry.py::test_registry_parity`` generates
it from the kernel registry's per-op example inputs and comparison specs.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.masked_matmul.ops import masked_matmul, tile_skip_fraction
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.stochastic_round.ops import stochastic_round


def test_sr_seed_changes_stream():
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,))
    a = stochastic_round(x, jnp.uint32(1), impl="ref")
    b = stochastic_round(x, jnp.uint32(2), impl="ref")
    assert not bool(jnp.all(a == b))


def qgrid(seed, shape, sparsity, fl=8):
    key = jax.random.PRNGKey(seed)
    v = jnp.round(jax.random.normal(key, shape) * 2**6) / 2**fl
    keep = jax.random.uniform(jax.random.fold_in(key, 1), shape) > sparsity
    return v * keep


def test_masked_matmul_tile_skip_preserves_results():
    """Block-pruned operands: >0 tiles skipped, results still exact."""
    x = qgrid(0, (256, 512), 0.3).at[:128, :256].set(0.0)
    w = qgrid(1, (512, 256), 0.3).at[256:, 128:].set(0.0)
    skip = float(tile_skip_fraction(x, w))
    assert skip >= 0.45
    a = masked_matmul(x, w, jnp.uint32(3), impl="interpret")
    b = masked_matmul(x, w, jnp.uint32(3), impl="ref")
    assert bool(jnp.all(a == b))


def test_masked_matmul_grad_path():
    """The quant training path wraps this op via STE at a higher level;
    the op itself must be usable inside jit."""
    x = qgrid(3, (64, 64), 0.5)
    w = qgrid(4, (64, 64), 0.5)
    y = jax.jit(lambda a, b: masked_matmul(a, b, impl="ref"))(x, w)
    assert y.shape == (64, 64) and bool(jnp.all(jnp.isfinite(y)))


def test_ssd_return_state_matches_sequential():
    """Prefill -> decode handoff: the returned state must equal the state
    the sequential recurrence reaches after S tokens."""
    B, S, H, P, G, N = 1, 256, 2, 32, 1, 16
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 2), (B, S, H)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 3), (H,)) * 0.3)
    b = jax.random.normal(jax.random.fold_in(key, 4), (B, S, G, N)) / 4
    c = jax.random.normal(jax.random.fold_in(key, 5), (B, S, G, N)) / 4
    _, state = ssd_scan(x, dt, a, b, c, impl="jnp", return_state=True)

    # sequential state
    bf = np.repeat(np.asarray(b), H // G, 2)
    st = np.zeros((B, H, N, P), np.float32)
    for t in range(S):
        alpha = np.exp(np.asarray(dt)[:, t] * np.asarray(a))
        st = st * alpha[..., None, None] + np.einsum(
            "bhn,bhp->bhnp", bf[:, t] * np.asarray(dt)[:, t][..., None], np.asarray(x)[:, t])
    np.testing.assert_allclose(np.asarray(state), st, rtol=2e-4, atol=1e-5)


def test_ssd_kernel_gradient_matches_sequential_oracle():
    """Training differentiates the Pallas scan (a pallas_call has no
    reverse-mode rule) through its custom VJP; every input's gradient
    must match autodiff of the sequential reference.  S=200 also covers
    the wrapper's chunk padding."""
    B, S, H, P, G, N = 1, 200, 4, 32, 2, 16
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 2), (B, S, H)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 3), (H,)) * 0.3)
    b = jax.random.normal(jax.random.fold_in(key, 4), (B, S, G, N)) / 4
    c = jax.random.normal(jax.random.fold_in(key, 5), (B, S, G, N)) / 4

    def grads(impl):
        loss = lambda *args: jnp.sum(ssd_scan(*args, impl=impl) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)

    for got, want in zip(grads("interpret"), grads("ref")):
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * scale
