"""Sparsity-aware backward kernels for the masked matmul (SPRING training).

SPRING's central claim is that binary-mask sparsity pays off *in training*:
activations stay ReLU-sparse, the ReLU VJP zeroes the cotangent wherever
the forward activation was zero (Sarma et al. 2021's activation-based
gradient output sparsity), so both backward GEMMs of ``y = x @ w``

  dL/dx = g @ w.T        (cotangent  x  transposed weights)
  dL/dw = x.T @ g        (stashed activation  x  cotangent)

inherit mask-structured sparsity and are served by the same tile-skipping
machinery as the forward pass.  This module registers them as first-class
registry ops (``masked_matmul_dx`` / ``masked_matmul_dw``):

  ref        dense fp32 transpose matmul (oracle; the CPU production path)
  interpret  the Pallas tile-skipping kernel in interpret mode (tests)
  pallas     the Pallas tile-skipping kernel (TPU)

Gradients are *not* SR-rounded here: SPRING accumulates gradients at MAC
width and applies stochastic rounding at the weight update (the optimizer's
job), so every impl runs the kernel with ``apply_sr=False`` and the
comparison contract is relative (fp32 summation-order slack), not exact.
Like the forward's, each lowering returns its product and the
``ops.call_counts`` of the kernel call.  ``ops.masked_matmul``'s
custom_vjp resolves both at the call (``ops.backward_lowerings``) and
runs them through ``ops.grad_product``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.masked_matmul import ops as mm_ops
from repro.kernels.masked_matmul.mm_kernel import TILE

__all__ = ["sparsity_probe"]


def _ref_dot(a: jax.Array, b: jax.Array):
    """Dense fp32 ``a @ b`` and the :func:`ops.call_counts` of the kernel
    call it stands for."""
    return (jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32)),
            mm_ops.call_counts(mm_ops.prepare(a, b)))


def _kernel_dot(a: jax.Array, b: jax.Array, *, interpret: bool):
    """The forward Pallas lowering reused with the SR epilogue disabled:
    tile-skipped fp32 accumulate of ``a @ b`` and its counts (same
    padding/occupancy geometry as the forward — single-sourced in
    ops._mm_kernel)."""
    return mm_ops._mm_kernel(a, b, jnp.uint32(0), apply_sr=False, interpret=interpret)


# dx: (M, N) cotangent x (K, N) weights -> (M, K)
@jax.jit
def _dx_ref(g, w):
    return _ref_dot(g, w.T)


@partial(jax.jit, static_argnames=("interpret",))
def _dx_kernel(g, w, *, interpret=False):
    return _kernel_dot(g, w.T, interpret=interpret)


# dw: (M, K) stashed activation x (M, N) cotangent -> (K, N)
@jax.jit
def _dw_ref(x, g):
    return _ref_dot(x.T, g)


@partial(jax.jit, static_argnames=("interpret",))
def _dw_kernel(x, g, *, interpret=False):
    return _kernel_dot(x.T, g, interpret=interpret)


# ---------------------------------------------------------------------------
# Registration: parity examples model the training shapes — a ReLU-masked
# cotangent against sparse weights/activations, dense and empty extremes.
# ---------------------------------------------------------------------------


def _sparse_mat(seed: int, shape, sparsity: float) -> jax.Array:
    key = jax.random.PRNGKey(seed)
    v = jax.random.normal(key, shape) * 0.1
    keep = jax.random.uniform(jax.random.fold_in(key, 1), shape) > sparsity
    return v * keep


def _dx_examples() -> list:
    cases = []
    for m, k, n, s in [(128, 128, 128, 0.5), (100, 70, 50, 0.3), (64, 200, 512, 0.7)]:
        g = _sparse_mat(m + n, (m, n), s)
        w = _sparse_mat(k * 3 + n, (k, n), s)
        cases.append(((g, w), {}))
    # whole-tile-sparse cotangent (block-pruned) and the all-zero extreme
    g = _sparse_mat(0, (256, 256), 0.2).at[:128, :].set(0.0)
    cases.append(((g, _sparse_mat(1, (256, 256), 0.2)), {}))
    cases.append(((jnp.zeros((64, 64)), _sparse_mat(2, (64, 64), 0.5)), {}))
    # blocks 512 x 512 x 384 on the one-dot path; one 256 x 384 x 512
    # block with an empty cotangent tile, on the sub-tile path
    cases.append(((_sparse_mat(6, (512, 384), 0.2), _sparse_mat(7, (512, 384), 0.2)), {}))
    g = _sparse_mat(8, (256, 512), 0.2).at[128:, :128].set(0.0)
    cases.append(((g, _sparse_mat(9, (384, 512), 0.2)), {}))
    # idle block steps: cotangent rows live in row blocks 1 and 3 of five
    # (leading, interior and trailing idle runs), and an all-empty one
    g = _sparse_mat(10, (1280, 384), 0.2)
    for lo, hi in ((0, 256), (320, 768), (896, 1280)):
        g = g.at[lo:hi].set(0.0)
    cases.append(((g, _sparse_mat(11, (768, 384), 0.2)), {}))
    cases.append(((jnp.zeros((1024, 256)), _sparse_mat(12, (384, 256), 0.2)), {}))
    return cases


def _dw_examples() -> list:
    cases = []
    for m, k, n, s in [(128, 128, 128, 0.5), (100, 70, 50, 0.3), (512, 64, 200, 0.7)]:
        x = _sparse_mat(m * 5 + k, (m, k), s)
        g = _sparse_mat(m + n * 7, (m, n), s)
        cases.append(((x, g), {}))
    x = _sparse_mat(3, (256, 384), 0.2).at[:, 256:].set(0.0)
    cases.append(((x, _sparse_mat(4, (256, 256), 0.2)), {}))
    cases.append(((_sparse_mat(5, (64, 64), 0.5), jnp.zeros((64, 64))), {}))
    # blocks 512 x 384 x 384 on the one-dot path; 512-row blocks, one
    # all-empty beside one on the one-dot path
    cases.append(((_sparse_mat(6, (384, 512), 0.2), _sparse_mat(7, (384, 768), 0.2)), {}))
    x = _sparse_mat(8, (256, 1024), 0.2).at[:, :512].set(0.0)
    cases.append(((x, _sparse_mat(9, (256, 256), 0.2)), {}))
    # an expert buffer cut to 128-multiples: rows 0-383 of 2048 live, so
    # the empty blocks lie along the kernel's K and k-steps 1-3 are idle;
    # and an all-empty activation
    x = _sparse_mat(10, (2048, 512), 0.2).at[384:].set(0.0)
    cases.append(((x, _sparse_mat(11, (2048, 384), 0.2).at[384:].set(0.0)), {}))
    cases.append(((jnp.zeros((1024, 256)), _sparse_mat(12, (1024, 384), 0.2)), {}))
    return cases


_BWD_COMPARE = {"kind": "rel", "tol": 1e-5}

registry.register_op("masked_matmul_dx", oracle="ref", examples=_dx_examples,
                     compare=_BWD_COMPARE)
registry.register_impl("masked_matmul_dx", "ref", priority=10)(_dx_ref)
registry.register_impl("masked_matmul_dx", "interpret", selectable=False)(
    partial(_dx_kernel, interpret=True))
registry.register_impl("masked_matmul_dx", "pallas", priority=30,
                       available=registry.on_tpu)(
    partial(_dx_kernel, interpret=False))

registry.register_op("masked_matmul_dw", oracle="ref", examples=_dw_examples,
                     compare=_BWD_COMPARE)
registry.register_impl("masked_matmul_dw", "ref", priority=10)(_dw_ref)
registry.register_impl("masked_matmul_dw", "interpret", selectable=False)(
    partial(_dw_kernel, interpret=True))
registry.register_impl("masked_matmul_dw", "pallas", priority=30,
                       available=registry.on_tpu)(
    partial(_dw_kernel, interpret=False))


def sparsity_probe(density: float = 0.5, size: int = 512,
                   seed: int = 0) -> dict:
    """Measured fwd/bwd tile-skip fractions at a given tile-granular density.

    Runs one eager ``masked_matmul`` forward + backward on ``size``-square
    operands whose 128x128 tiles are kept with probability ``density``
    (block-pruned operands — the granularity SPRING's pre-compute module
    skips at), and reports what the instrumentation hooks measured.  The
    dry-run embeds this in its JSON so backward tile-skip is attributable
    per cell even though the lowered program itself never executes there.
    """
    key = jax.random.PRNGKey(seed)

    def tile_sparse(k, shape):
        v = jax.random.normal(k, shape) * 0.05
        keep = jax.random.uniform(
            jax.random.fold_in(k, 1), (shape[0] // TILE, shape[1] // TILE)
        ) < density
        if density < 1.0:  # at least one skippable tile per operand
            keep = keep.at[0, 0].set(False)
        return v * jnp.repeat(jnp.repeat(keep, TILE, 0), TILE, 1)

    x = tile_sparse(jax.random.fold_in(key, 0), (size, size))
    w = tile_sparse(jax.random.fold_in(key, 1), (size, size))

    def loss(x, w):
        y = mm_ops.masked_matmul(x, w, apply_sr=False)
        return jnp.sum(jax.nn.relu(y) ** 2)

    with registry.record_kernel_metrics() as rows:
        mm_ops.masked_matmul(x, w, apply_sr=False)  # eager fwd: records skip
        jax.grad(loss, argnums=(0, 1))(x, w)        # eager bwd: dx/dw skips
    s = registry.metric_summary(rows)
    dx = s.get("masked_matmul_dx", {}).get("tile_skip")
    dw = s.get("masked_matmul_dw", {}).get("tile_skip")
    bwd = [v for v in (dx, dw) if v is not None]
    return {
        "density": density,
        "size": size,
        "forward_tile_skip": s.get("masked_matmul", {}).get("tile_skip"),
        "backward_tile_skip_dx": dx,
        "backward_tile_skip_dw": dw,
        "backward_tile_skip": sum(bwd) / len(bwd) if bwd else None,
    }
