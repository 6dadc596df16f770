"""Statistical seal on the stochastic-rounding unit (ISSUE 3, satellite 3):
the paper's "no accuracy loss" claim rests on SR being unbiased
(E[Round(x)] = x, Eq. 4) — verified here within CLT bounds over >=10k
draws for both the PRNG-key quantizer and the counter-hash kernel op, plus
determinism under a fixed key/seed."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fixedpoint import SPRING_FORMAT, quantize_stochastic
from repro.kernels.stochastic_round.ops import stochastic_round

N_DRAWS = 20_000
SIGMAS = 5.0  # false-failure odds ~ 1 in 1.7M per check


def _clt_bound(frac: float, eps: float, n: int) -> float:
    """SIGMAS-sigma bound on |mean - x|: one draw deviates by eps with
    variance eps^2 * frac * (1 - frac)."""
    return SIGMAS * eps * np.sqrt(max(frac * (1.0 - frac), 1e-12) / n)


def test_quantize_stochastic_mean_is_unbiased_within_clt():
    eps = SPRING_FORMAT.eps
    for frac, seed in [(0.3, 0), (0.5, 1), (0.77, 2), (0.05, 3)]:
        x = jnp.full((N_DRAWS,), 0.5 + frac * eps, jnp.float32)
        q = quantize_stochastic(jax.random.PRNGKey(seed), x)
        # float64 mean: a float32 mean of 20k draws errs by more than the
        # CLT bound at small fractions
        bias = float(np.asarray(q, np.float64).mean() - float(x[0]))
        assert abs(bias) <= _clt_bound(frac, eps, N_DRAWS), (frac, bias)
        # every draw lands on one of the two neighboring grid points
        lo = np.floor(0.5 / eps + frac) * eps
        assert set(np.unique(np.asarray(q))) <= {np.float32(lo),
                                                 np.float32(lo + eps)}


def test_stochastic_round_kernel_mean_is_unbiased_within_clt():
    """The counter-hash (LFSR stand-in) kernel op is unbiased too: its
    per-element streams are independent across the >=10k lanes."""
    eps = 2.0 ** -16
    for frac, seed in [(0.25, 9), (0.5, 10), (0.9, 11)]:
        x = jnp.full((N_DRAWS,), 1.0 + frac * eps, jnp.float32)
        q = stochastic_round(x, jnp.uint32(seed))
        bias = float(q.mean() - x[0])
        assert abs(bias) <= _clt_bound(frac, eps, N_DRAWS), (frac, bias)


def test_stochastic_round_probability_matches_fraction():
    """P(round up) tracks the fractional part (Eq. 4), not just the mean."""
    eps = 2.0 ** -16
    for frac in (0.2, 0.5, 0.8):
        x = jnp.full((N_DRAWS,), 2.0 + frac * eps, jnp.float32)
        q = stochastic_round(x, jnp.uint32(42))
        up = float((q > x[0]).mean())
        assert abs(up - frac) <= SIGMAS * np.sqrt(frac * (1 - frac) / N_DRAWS)


def test_stochastic_rounding_is_deterministic_under_fixed_key():
    x = jax.random.normal(jax.random.PRNGKey(7), (4096,)) * 2
    a = quantize_stochastic(jax.random.PRNGKey(3), x)
    b = quantize_stochastic(jax.random.PRNGKey(3), x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # different keys produce different draws on in-between values
    c = quantize_stochastic(jax.random.PRNGKey(4), x)
    assert np.any(np.asarray(a) != np.asarray(c))

    ka = stochastic_round(x, jnp.uint32(5))
    kb = stochastic_round(x, jnp.uint32(5))
    np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb))
    kc = stochastic_round(x, jnp.uint32(6))
    assert np.any(np.asarray(ka) != np.asarray(kc))
