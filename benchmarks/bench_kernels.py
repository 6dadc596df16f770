"""Kernel microbenchmarks (CPU wall time of the registry-resolved paths +
interpret-mode functional checks; TPU perf comes from the §Roofline
dry-run, not here).

Rows: us_per_call = wall time; derived = a kernel-specific figure of merit
(tile-skip fraction, GFLOP count, rel-err vs oracle); impl = the impl the
kernel registry resolved for the call, so BENCH trajectories are
attributable to a backend.

``--smoke`` sweeps every registered (op, impl) pair runnable on the
current backend through the registry's example inputs and cross-checks
each against the op's oracle — the CI kernel-parity job runs this, so a
kernel cannot ship without registering.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.masked_matmul.ops import (
    grad_product,
    masked_matmul,
    tile_skip_fraction,
)
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.stochastic_round.ops import stochastic_round


def _time(fn, *args, iters: int = 10, **kw) -> float:
    jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args, **kw))
    return (time.perf_counter() - t0) / iters * 1e6


def _resolved(op: str) -> str:
    # planning lookup for row attribution; the timed call itself counts
    return registry.resolve(op, _count=False).name


def _grad(op: str):
    """The backward product of ``op`` (masked_matmul_dx / _dw) through
    its resolved lowering, as the training step runs it."""
    lowering = registry.resolve(op, _count=False)
    return lambda a, b: grad_product(lowering, a, b)[0]


def rows() -> list[tuple]:
    out = []
    key = jax.random.PRNGKey(0)

    x = jax.random.normal(key, (512, 1024))
    us = _time(stochastic_round, x, jnp.uint32(1))
    out.append(("kernel.stochastic_round.512x1024", us, x.size / 1e6,
                _resolved("stochastic_round")))

    # block-sparse fixed-point matmul: 50% of 128-tiles pruned
    m = k = n = 512
    a = jnp.round(jax.random.normal(key, (m, k)) * 64) / 256
    w = jnp.round(jax.random.normal(jax.random.fold_in(key, 1), (k, n)) * 64) / 256
    a = a.at[:256, :256].set(0.0)
    w = w.at[256:, 256:].set(0.0)
    us = _time(masked_matmul, a, w, jnp.uint32(3))
    skip = float(tile_skip_fraction(a, w))
    out.append(("kernel.masked_matmul.512cube", us, skip,
                _resolved("masked_matmul")))

    # the backward GEMMs of the same layer: a ReLU-masked cotangent (top
    # half of the 128-tiles zeroed) against the sparse weights/activation —
    # derived = measured backward tile-skip fraction
    g = jnp.round(jax.random.normal(jax.random.fold_in(key, 8), (m, n)) * 64) / 256
    g = g.at[:256, :].set(0.0)
    us = _time(_grad("masked_matmul_dx"), g, w)
    out.append(("kernel.masked_matmul_dx.512cube", us,
                float(tile_skip_fraction(g, w.T)),
                _resolved("masked_matmul_dx")))
    us = _time(_grad("masked_matmul_dw"), a, g)
    out.append(("kernel.masked_matmul_dw.512cube", us,
                float(tile_skip_fraction(a.T, g)),
                _resolved("masked_matmul_dw")))

    q = jax.random.normal(key, (1, 4, 512, 64))
    kk = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 512, 64))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 2, 512, 64))
    us = _time(flash_attention, q, kk, v, causal=True)
    flops = 4 * 1 * 4 * 512 * 512 * 64 / 2  # causal half
    out.append(("kernel.flash_attention.b1h4s512", us, flops / 1e9,
                _resolved("flash_attention")))

    xs = jax.random.normal(key, (2, 512, 8, 64))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 4), (2, 512, 8)))
    aa = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 5), (8,)) * 0.3)
    b = jax.random.normal(jax.random.fold_in(key, 6), (2, 512, 2, 64)) / 8
    c = jax.random.normal(jax.random.fold_in(key, 7), (2, 512, 2, 64)) / 8
    us = _time(ssd_scan, xs, dt, aa, b, c)
    ref = ssd_scan(xs, dt, aa, b, c, impl="ref")
    got = ssd_scan(xs, dt, aa, b, c)
    rel = float(jnp.max(jnp.abs(got - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    out.append(("kernel.ssd_scan.b2s512h8", us, rel, _resolved("ssd_scan")))
    return out


# ---------------------------------------------------------------------------
# Registry parity smoke: the CI sweep over every registered (op, impl).
# ---------------------------------------------------------------------------


def smoke_rows() -> tuple[list[tuple], list[str]]:
    """One row per registered (op, impl) pair runnable here, parity-checked
    against the op's oracle on its registered example inputs.  A failing
    pair does not abort the sweep: it is reported in the returned failure
    list (and its row carries derived=nan)."""
    out = []
    failures = []
    for op, impl in registry.parity_pairs():
        spec = registry.op_spec(op)
        if spec.examples is None:
            continue
        cases = spec.examples()
        worst = 0.0
        t0 = time.perf_counter()
        try:
            for case in cases:
                args, kwargs = case[0], case[1]
                case_cmp = case[2] if len(case) > 2 else None
                oracle_fn = registry.impls(op)[spec.oracle].fn
                impl_fn = registry.impls(op)[impl].fn
                want = oracle_fn(*args, **kwargs)
                got = impl_fn(*args, **kwargs)
                worst = max(worst, registry.compare_outputs(op, got, want, case_cmp))
        except Exception as e:  # parity violation or impl crash
            failures.append(f"{op}.{impl}: {e}")
            worst = float("nan")
        us = (time.perf_counter() - t0) / max(len(cases), 1) * 1e6
        out.append((f"kernel.parity.{op}.{impl}", us, worst, impl))
    return out, failures


def main() -> None:
    smoke = "--smoke" in sys.argv
    print("name,us_per_call,derived,impl")
    failures = []
    if smoke:
        smoke_out, failures = smoke_rows()
        if not smoke_out:
            failures.append("registry reports no parity pairs — registration broken?")
        for name, us, derived, impl in smoke_out:
            print(f"{name},{us:.2f},{derived:.6g},{impl}")
    else:
        for name, us, derived, impl in rows():
            print(f"{name},{us:.2f},{derived:.6g},{impl}")
    for f in failures:
        print(f"PARITY FAILURE: {f}", file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
