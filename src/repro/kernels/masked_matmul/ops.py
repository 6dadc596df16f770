"""Public wrapper for the sparsity-aware fixed-point matmul.

Handles padding to MXU tiles, occupancy-mask computation (the packed
binary masks AND-reduced per tile — SPRING's pre-compute sparsity stage),
and registers its implementations with ``repro.kernels.registry``:

  ref        dense f32 matmul + identical SR epilogue (vectorized oracle;
             the CPU production path)
  interpret  the Pallas kernel in interpret mode (tests)
  pallas     the Pallas kernel (TPU)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.masked_matmul.mm_kernel import BK, BM, BN, masked_matmul_pallas, padded_dims
from repro.kernels.masked_matmul.ref import masked_matmul_reference


def _occupancy(a: jax.Array, tm: int, tn: int) -> jax.Array:
    m, n = a.shape
    t = a.reshape(m // tm, tm, n // tn, tn)
    return jnp.any(t != 0.0, axis=(1, 3)).astype(jnp.int32)


def prepare(x: jax.Array, w: jax.Array):
    """The pre-compute sparsity stage of ``x @ w``: both operands padded to
    MXU tiles in float32, and their occupancy tables ``x_occ`` (Mi, Kk)
    and ``w_occ`` (Kk, Nj), int32 (1 where a tile holds a non-zero)."""
    with jax.named_scope("spring_mm_prep"):
        m, k = x.shape
        _, n = w.shape
        m_pad, n_pad, k_pad = padded_dims(m, n, k)
        xp = jnp.pad(x.astype(jnp.float32), ((0, m_pad - m), (0, k_pad - k)))
        wp = jnp.pad(w.astype(jnp.float32), ((0, k_pad - k), (0, n_pad - n)))
        return xp, wp, _occupancy(xp, BM, BK), _occupancy(wp, BK, BN)


def tile_counts(x: jax.Array, w: jax.Array) -> jax.Array:
    """``[issued, total]`` MXU grid steps of ``x @ w`` as float32: a step
    (i, j, k) is issued when ``x_occ[i, k] AND w_occ[k, j]``; padding
    tiles count.  Built from :func:`prepare`, so inside a jitted program
    the tables are the ones the kernel's wrapper builds (XLA merges the
    two identical computations)."""
    _, _, x_occ, w_occ = prepare(x, w)
    with jax.named_scope("spring_mm_prep"):
        issued = jnp.einsum("ik,kj->", x_occ.astype(jnp.float32),
                            w_occ.astype(jnp.float32))
        total = x_occ.shape[0] * w_occ.shape[0] * w_occ.shape[1]
        return jnp.stack([issued, jnp.float32(total)])


@partial(jax.jit, static_argnames=("il", "fl", "apply_sr"))
def _mm_ref(x, w, seed, *, il=4, fl=16, apply_sr=True):
    return masked_matmul_reference(x, w, seed, il=il, fl=fl, apply_sr=apply_sr)


@partial(jax.jit, static_argnames=("il", "fl", "apply_sr", "interpret"))
def _mm_kernel(x, w, seed, *, il=4, fl=16, apply_sr=True, interpret=False):
    xp, wp, x_occ, w_occ = prepare(x, w)
    out = masked_matmul_pallas(
        xp, wp, x_occ, w_occ, seed,
        il=il, fl=fl, apply_sr=apply_sr, interpret=interpret,
    )
    return out[:x.shape[0], :w.shape[1]]


def _example_operands(seed: int, shape, sparsity: float = 0.5, fl: int = 8):
    key = jax.random.PRNGKey(seed)
    v = jnp.round(jax.random.normal(key, shape) * 2**6) / 2**fl
    keep = jax.random.uniform(jax.random.fold_in(key, 1), shape) > sparsity
    return v * keep


def _examples() -> list:
    cases = []
    for m, k, n in [(128, 128, 128), (100, 70, 50), (64, 512, 200)]:
        x = _example_operands(m * 7 + k, (m, k))
        w = _example_operands(n * 13 + k, (k, n))
        cases.append(((x, w, jnp.uint32(5)), {}))
    # block-pruned operands: whole MXU tiles skipped, plus the SR-off path
    x = _example_operands(0, (256, 384), 0.3).at[:128, :256].set(0.0)
    w = _example_operands(1, (384, 256), 0.3).at[256:, 128:].set(0.0)
    cases.append(((x, w, jnp.uint32(3)), {}))
    cases.append(((x, w, jnp.uint32(3)), {"apply_sr": False},
                  {"kind": "allclose", "atol": 1e-6, "rtol": 0.0}))
    return cases


registry.register_op("masked_matmul", oracle="ref", examples=_examples,
                     compare={"kind": "exact"})
registry.register_impl("masked_matmul", "ref", priority=10)(_mm_ref)
registry.register_impl("masked_matmul", "interpret", selectable=False)(
    partial(_mm_kernel, interpret=True))
registry.register_impl("masked_matmul", "pallas", priority=30,
                       available=registry.on_tpu)(
    partial(_mm_kernel, interpret=False))


def masked_matmul(
    x: jax.Array,
    w: jax.Array,
    seed: jax.Array | None = None,
    *,
    il: int = 4,
    fl: int = 16,
    apply_sr: bool = True,
    impl: str | None = None,
    backward: str | None = None,
    probe: jax.Array | None = None,
) -> jax.Array:
    """Sparsity-aware ``x @ w`` on the Q(il,fl) grid with SR epilogue.

    x: (M, K) float32 grid values (zeros = skippable); w: (K, N).
    ``impl`` pins a registered implementation; None defers to the active
    :class:`~repro.kernels.registry.KernelPolicy`.

    ``backward`` selects the sparsity-aware training direction: None/"none"
    differentiates through the resolved forward impl (dense autodiff; the
    Pallas paths are not differentiable), while "auto" or a concrete impl
    name wraps the call in a ``custom_vjp`` whose dL/dx / dL/dw are the
    registry-resolved ``masked_matmul_dx`` / ``masked_matmul_dw`` kernels —
    tile skipping applies in both directions (DESIGN.md §8).  ``probe``
    is that path's tile counter (``backward.mm_call_with_backward``).
    """
    if seed is None:
        seed = jnp.uint32(0)
    kimpl = registry.resolve("masked_matmul", impl)
    if registry.metrics_active() and not isinstance(x, jax.core.Tracer) \
            and not isinstance(w, jax.core.Tracer):
        registry.note_metric("masked_matmul",
                             tile_skip=float(tile_skip_fraction(x, w)))
    if backward in (None, "none"):
        return kimpl.fn(x, w, seed, il=il, fl=fl, apply_sr=apply_sr)
    from repro.kernels.masked_matmul.backward import mm_call_with_backward

    return mm_call_with_backward(x, w, seed, il=il, fl=fl, apply_sr=apply_sr,
                                 fwd_impl=kimpl.name, bwd_impl=backward,
                                 probe=probe)


def tile_skip_fraction(x: jax.Array, w: jax.Array) -> jax.Array:
    """Fraction of (i,j,k) MXU grid steps skipped for these operands.

    The roofline compute-term scales by (1 - skip_fraction) on TPU; this
    is the analytically-reportable speedup of the kernel (§Perf).
    """
    issued, total = tile_counts(x, w)
    return 1.0 - issued / total
