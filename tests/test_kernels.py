"""Kernel behavioral tests (SR stream properties, tile-skip invariance,
grad-path usability, SSD state handoff).

Oracle parity for every registered (op, impl) pair is NOT enumerated here
any more: ``tests/test_kernel_registry.py::test_registry_parity`` generates
it from the kernel registry's per-op example inputs and comparison specs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.masked_matmul.mm_kernel import (
    BLOCKS, TILE, block_dims, fetch_bits, padded_dims)
from repro.kernels.masked_matmul.ops import (
    block_counts, masked_matmul, prepare, tile_skip_fraction)
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


@pytest.mark.parametrize("mkn,blocks", [
    ((2048, 1536, 6448), (512, 384, 512)),   # mamba2-780m in_proj forward
    ((2048, 3072, 1536), (512, 512, 512)),   # out_proj forward
    ((2048, 6448, 1536), (512, 512, 384)),   # in_proj dx: g @ w.T
    ((1536, 2048, 6448), (512, 384, 512)),   # in_proj dw: x.T @ g
    ((2048, 2048, 8192), (512, 512, 512)),   # llama3.2-1b MLP up
    ((64, 70, 200), (128, 256, 128)),        # decode-sized M keeps 128 rows
])
def test_masked_matmul_block_rule(mkn, blocks):
    """The kernel's blocks, from the 128-padded (M, N, K) alone."""
    m, k, n = mkn
    assert block_dims(*padded_dims(m, n, k)) == blocks


def test_masked_matmul_blocks_divide_and_fit():
    """Every block divides its padded dim and is a whole number of tiles,
    and the largest blocks' pipelined buffers (x, w and out in float32,
    double-buffered) stay under v5e's default scoped VMEM limit of 16 MiB."""
    for d in range(TILE, 64 * TILE + 1, TILE):
        for b in block_dims(d, d, d):
            assert b % TILE == 0 and d % b == 0 and b <= max(BLOCKS)
    bm = bn = bk = max(BLOCKS)
    assert 2 * 4 * (bm * bk + bk * bn + bm * bn) <= 6 * 2**20 < 16 * 2**20


def _grid_fetch(x, w):
    """Brute force over the kernel's grid in row-major (I, J, K) order:
    whether each block step issues a dot (some 128-sub-tile with x and w
    both occupied), and the step whose blocks it loads (the last working
    step at or before it, else the first working step, else 0)."""
    x, w = np.asarray(x), np.asarray(w)
    (m, k), n = x.shape, w.shape[1]
    m_pad, n_pad, k_pad = padded_dims(m, n, k)
    bm, bn, bk = block_dims(m_pad, n_pad, k_pad)
    xp = np.zeros((m_pad, k_pad))
    xp[:m, :k] = x
    wp = np.zeros((k_pad, n_pad))
    wp[:k, :n] = w

    def occupied(a, r, c):
        return bool(np.any(a[r * TILE:(r + 1) * TILE, c * TILE:(c + 1) * TILE] != 0))

    work = []
    for bi in range(m_pad // bm):
        for bj in range(n_pad // bn):
            for bkk in range(k_pad // bk):
                work.append(any(
                    occupied(xp, bi * bm // TILE + a, bkk * bk // TILE + c)
                    and occupied(wp, bkk * bk // TILE + c, bj * bn // TILE + b)
                    for a in range(bm // TILE) for b in range(bn // TILE)
                    for c in range(bk // TILE)))
    first = work.index(True) if any(work) else 0
    fetch, last = [], None
    for s, works in enumerate(work):
        last = s if works else last
        fetch.append(first if last is None else last)
    return work, fetch


def _fetch_cases():
    full = qgrid(10, (1024, 768), 0.0) + 1.0
    between = qgrid(11, (2560, 768), 0.3)
    # live rows 512:640 and 1536:1664: row blocks 1 and 3 of 5
    for lo, hi in ((0, 512), (640, 1536), (1664, 2560)):   # leading, interior, trailing
        between = between.at[lo:hi].set(0.0)
    expert = qgrid(12, (2048, 512), 0.3).at[384:].set(0.0)
    return {
        "full": (full, qgrid(13, (768, 1024), 0.0) + 1.0),
        "rows_between_empty": (between, qgrid(14, (768, 768), 0.3)),
        "empty_k_blocks": (expert.T, qgrid(15, (2048, 384), 0.3).at[384:].set(0.0)),
        "all_empty": (jnp.zeros((1024, 256)), qgrid(16, (256, 384), 0.3)),
        "w_tile_holes": (qgrid(17, (512, 1024), 0.3),
                         qgrid(18, (1024, 768), 0.3).at[:512, :384].set(0.0)),
    }


@pytest.mark.parametrize("case", list(_fetch_cases()))
def test_masked_matmul_fetch_table(case):
    """``prepare``'s work and fetch tables against a loop over the grid: a working
    step loads its own blocks, an idle step the previous step's (so the
    pipeline copies nothing for it), fully occupied operands give the
    identity; ``block_counts``' idle entry counts the idle steps."""
    x, w = _fetch_cases()[case]
    work, want = _grid_fetch(x, w)
    plan = prepare(x, w)
    nj, kk = plan.w_full.shape[1], plan.x_full.shape[1]
    jb, kb = fetch_bits(nj, kk)
    # unpack (I', J', K') into the flat row-major step
    fetch = [((f >> (jb + kb)) * nj + ((f >> kb) & ((1 << jb) - 1))) * kk
             + (f & ((1 << kb) - 1)) for f in np.asarray(plan.fetch).tolist()]
    assert np.asarray(plan.work).reshape(-1).tolist() == work
    assert fetch == want
    for s, works in enumerate(work):
        if works:
            assert fetch[s] == s
        elif s > 0:
            assert fetch[s] == fetch[s - 1]
    idle = work.count(False)
    assert np.asarray(block_counts(plan))[1:].tolist() == [len(work), idle]
    if case == "full":
        assert idle == 0 and fetch == list(range(len(work)))
    if case == "all_empty":
        assert idle == len(work) and fetch == [0] * len(work)
    if case in ("rows_between_empty", "empty_k_blocks"):
        assert 0 < idle < len(work)


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
