"""Sparsity-aware backward kernels for the masked matmul (SPRING training).

SPRING's central claim is that binary-mask sparsity pays off *in training*:
activations stay ReLU-sparse, the ReLU VJP zeroes the cotangent wherever
the forward activation was zero (Sarma et al. 2021's activation-based
gradient output sparsity), so both backward GEMMs of ``y = x @ w``

  dL/dx = g @ w.T        (cotangent  x  transposed weights)
  dL/dw = x.T @ g        (stashed activation  x  cotangent)

inherit mask-structured sparsity and are served by the same tile-skipping
machinery as the forward pass.  This module registers them as first-class
registry ops (``masked_matmul_dx`` / ``masked_matmul_dw``) with the full
impl ladder:

  ref        dense fp32 transpose matmul (oracle; the CPU production path)
  jnp        occupancy-gated block einsum — the vectorized lowering that
             materializes the tile-AND gate explicitly (numerics-identical:
             a gated-out tile contributes exactly +0.0)
  interpret  the Pallas tile-skipping kernel in interpret mode (tests)
  pallas     the Pallas tile-skipping kernel (TPU)

Gradients are *not* SR-rounded here: SPRING accumulates gradients at MAC
width and applies stochastic rounding at the weight update (the optimizer's
job), so every impl runs the kernel with ``apply_sr=False`` and the
comparison contract is relative (fp32 summation-order slack), not exact.

``mm_call_with_backward`` is the ``jax.custom_vjp`` that ``ops.masked_matmul``
routes through when a ``backward=`` policy is given: forward runs the
registry-resolved forward impl unchanged; backward resolves dx/dw through
the registry so ``--backward-sparsity`` / ``KernelPolicy`` pins apply to the
training direction independently of the forward one.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import registry
from repro.kernels.masked_matmul import ops as mm_ops
from repro.kernels.masked_matmul.mm_kernel import TILE

__all__ = [
    "masked_matmul_dx",
    "masked_matmul_dw",
    "mm_call_with_backward",
    "backward_tile_skip",
    "sparsity_probe",
    "PROBE_SIZE",
]


# ---------------------------------------------------------------------------
# Shared lowerings.  Both backward ops are (A, B) -> A' @ B' for a fixed
# transpose pattern, so each impl is one parameterized function.
# ---------------------------------------------------------------------------


def _dense_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))


def _blocked_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """Occupancy-gated block matmul: the vectorized (jnp) realization of
    SPRING's tile-AND gate.  Tiles whose joint occupancy is empty are
    multiplied by a 0.0 gate, contributing exactly +0.0 to the fp32
    accumulator — same numerics contract as the Pallas kernel's skip."""
    ap, bp, a_occ, b_occ, _, _, _ = mm_ops.prepare(a, b)  # (Mi, Kk), (Kk, Nj)
    (m_pad, k_pad), n_pad = ap.shape, bp.shape[1]
    with jax.named_scope("spring_mm_prep"):
        at = ap.reshape(m_pad // TILE, TILE, k_pad // TILE, TILE).transpose(0, 2, 1, 3)
        bt = bp.reshape(k_pad // TILE, TILE, n_pad // TILE, TILE).transpose(0, 2, 1, 3)
        gate = (a_occ[:, :, None] & b_occ[None, :, :]).astype(jnp.float32)
    out = jnp.einsum("ikab,kjbc,ikj->ijac", at, bt, gate)
    return out.transpose(0, 2, 1, 3).reshape(m_pad, n_pad)[:a.shape[0], :b.shape[1]]


def _kernel_dot(a: jax.Array, b: jax.Array, *, interpret: bool) -> jax.Array:
    """The forward Pallas lowering reused with the SR epilogue disabled:
    tile-skipped fp32 accumulate of ``a @ b`` (same padding/occupancy
    geometry as the forward — single-sourced in ops._mm_kernel)."""
    return mm_ops._mm_kernel(a, b, jnp.uint32(0), apply_sr=False,
                             interpret=interpret)


# dx: (M, N) cotangent x (K, N) weights -> (M, K)
@partial(jax.jit, static_argnames=("il", "fl"))
def _dx_ref(g, w, *, il=4, fl=16):
    del il, fl  # gradients stay fp32; SR happens at the weight update
    return _dense_dot(g, w.T)


@partial(jax.jit, static_argnames=("il", "fl"))
def _dx_jnp(g, w, *, il=4, fl=16):
    del il, fl
    return _blocked_dot(g, w.T)


@partial(jax.jit, static_argnames=("il", "fl", "interpret"))
def _dx_kernel(g, w, *, il=4, fl=16, interpret=False):
    del il, fl
    return _kernel_dot(g, w.T, interpret=interpret)


# dw: (M, K) stashed activation x (M, N) cotangent -> (K, N)
@partial(jax.jit, static_argnames=("il", "fl"))
def _dw_ref(x, g, *, il=4, fl=16):
    del il, fl
    return _dense_dot(x.T, g)


@partial(jax.jit, static_argnames=("il", "fl"))
def _dw_jnp(x, g, *, il=4, fl=16):
    del il, fl
    return _blocked_dot(x.T, g)


@partial(jax.jit, static_argnames=("il", "fl", "interpret"))
def _dw_kernel(x, g, *, il=4, fl=16, interpret=False):
    del il, fl
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
registry.register_impl("masked_matmul_dx", "jnp", priority=5)(_dx_jnp)
registry.register_impl("masked_matmul_dx", "interpret", selectable=False)(
    partial(_dx_kernel, interpret=True))
registry.register_impl("masked_matmul_dx", "pallas", priority=30,
                       available=registry.on_tpu)(
    partial(_dx_kernel, interpret=False))

registry.register_op("masked_matmul_dw", oracle="ref", examples=_dw_examples,
                     compare=_BWD_COMPARE)
registry.register_impl("masked_matmul_dw", "ref", priority=10)(_dw_ref)
registry.register_impl("masked_matmul_dw", "jnp", priority=5)(_dw_jnp)
registry.register_impl("masked_matmul_dw", "interpret", selectable=False)(
    partial(_dw_kernel, interpret=True))
registry.register_impl("masked_matmul_dw", "pallas", priority=30,
                       available=registry.on_tpu)(
    partial(_dw_kernel, interpret=False))


# ---------------------------------------------------------------------------
# Public wrappers (registry-dispatched, instrumented).
# ---------------------------------------------------------------------------


def backward_tile_skip(a: jax.Array, b: jax.Array) -> jax.Array:
    """Tile-skip fraction of one backward GEMM ``a @ b`` (pre-transpose
    operands already applied) — the backward counterpart of
    ``ops.tile_skip_fraction``, shared MXU tile geometry."""
    return mm_ops.tile_skip_fraction(a, b)


def _note_skip(op: str, a: jax.Array, b: jax.Array) -> None:
    if registry.metrics_active() and not isinstance(a, jax.core.Tracer) \
            and not isinstance(b, jax.core.Tracer):
        registry.note_metric(op, tile_skip=float(backward_tile_skip(a, b)))


def masked_matmul_dx(g: jax.Array, w: jax.Array, *, il: int = 4, fl: int = 16,
                     impl: str | None = None) -> jax.Array:
    """dL/dx = g @ w.T through a registry-resolved sparsity-aware kernel.

    g: (M, N) cotangent (ReLU-masked positions are structural zeros);
    w: (K, N) weights.  Returns (M, K) fp32.
    """
    kimpl = registry.resolve("masked_matmul_dx", impl)
    _note_skip("masked_matmul_dx", g, w.T)
    return kimpl.fn(g, w, il=il, fl=fl)


def masked_matmul_dw(x: jax.Array, g: jax.Array, *, il: int = 4, fl: int = 16,
                     impl: str | None = None) -> jax.Array:
    """dL/dw = x.T @ g through a registry-resolved sparsity-aware kernel.

    x: (M, K) forward activation (the stashed sparse tensor the backward
    pass re-reads); g: (M, N) cotangent.  Returns (K, N) fp32.
    """
    kimpl = registry.resolve("masked_matmul_dw", impl)
    _note_skip("masked_matmul_dw", x.T, g)
    return kimpl.fn(x, g, il=il, fl=fl)


# ---------------------------------------------------------------------------
# The custom_vjp the public ``masked_matmul`` wrapper routes through.
# ---------------------------------------------------------------------------


def _float0_zero(seed: jax.Array):
    # integer primal -> float0 cotangent (custom_vjp contract for int args)
    return np.zeros(np.shape(seed), dtype=jax.dtypes.float0)


#: Length of the tile probe: the ``[issued, total]`` 128-tile steps of
#: the forward, dx and dw calls, in that order, then their ``[one_dot,
#: total, idle]`` block grid steps summed (see ``ops.probe_counts``).
PROBE_SIZE = 9


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _mm_bw(x, w, seed, probe, il, fl, apply_sr, fwd_impl, bwd_impl):
    del probe
    return registry.impls("masked_matmul")[fwd_impl].fn(
        x, w, seed, il=il, fl=fl, apply_sr=apply_sr)


def _mm_bw_fwd(x, w, seed, probe, il, fl, apply_sr, fwd_impl, bwd_impl):
    del probe
    y = registry.impls("masked_matmul")[fwd_impl].fn(
        x, w, seed, il=il, fl=fl, apply_sr=apply_sr)
    # Residual: the (sparse) operands only — never the dense accumulator —
    # plus the forward's tile and block counts.  The SR epilogue is
    # straight-through in the backward (DESIGN.md §8): range clipping is
    # handled by the caller's STE quantizer, keeping the residual at
    # exactly what SPRING's stash stores.
    return y, (x, w, seed, mm_ops.call_counts(x, w))


def _mm_bw_bwd(il, fl, apply_sr, fwd_impl, bwd_impl, res, g):
    x, w, seed, fwd_counts = res
    impl = None if bwd_impl == "auto" else bwd_impl
    dx = masked_matmul_dx(g, w, il=il, fl=fl, impl=impl)
    dw = masked_matmul_dw(x, g, il=il, fl=fl, impl=impl)
    # the probe's cotangent carries this call's tile counts out of the
    # backward pass; autodiff sums it over every call of the step
    counts = mm_ops.probe_counts(fwd_counts, mm_ops.call_counts(g, w.T),
                                 mm_ops.call_counts(x.T, g))
    return dx, dw, _float0_zero(seed), counts


_mm_bw.defvjp(_mm_bw_fwd, _mm_bw_bwd)


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
        y = mm_ops.masked_matmul(x, w, apply_sr=False, backward="auto")
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


def mm_call_with_backward(
    x: jax.Array,
    w: jax.Array,
    seed: jax.Array,
    *,
    il: int,
    fl: int,
    apply_sr: bool,
    fwd_impl: str,
    bwd_impl: str,
    probe: jax.Array | None = None,
) -> jax.Array:
    """Forward through ``fwd_impl`` with dx/dw routed through the
    sparsity-aware backward ops (``bwd_impl``: "auto" or a concrete name).

    ``probe`` is the tile counter: a float32 vector of ``PROBE_SIZE`` that
    the result does not depend on; its gradient is this call's
    ``[fwd_issued, fwd_total, dx_issued, dx_total, dw_issued, dw_total,
    one_dot_blocks, blocks, idle_blocks]`` (see ``ops.probe_counts``).
    Differentiating a whole program with respect to one probe shared by
    every call sums them, under ``scan``, remat and ``jit`` alike.

    A concrete ``bwd_impl`` is validated eagerly so a bad pin fails at the
    call site, not inside the backward trace.
    """
    if bwd_impl != "auto":
        registry.resolve("masked_matmul_dx", bwd_impl, _count=False)
        registry.resolve("masked_matmul_dw", bwd_impl, _count=False)
    if probe is None:
        probe = jnp.zeros((PROBE_SIZE,), jnp.float32)
    return _mm_bw(x, w, seed, probe, il, fl, apply_sr, fwd_impl, bwd_impl)
