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
from repro.kernels.masked_matmul.mm_kernel import (
    TILE, block_dims, fetch_bits, masked_matmul_pallas, padded_dims)
from repro.kernels.masked_matmul.ref import masked_matmul_reference


def _occupancy(a: jax.Array, tm: int, tn: int) -> jax.Array:
    m, n = a.shape
    t = a.reshape(m // tm, tm, n // tn, tn)
    return jnp.any(t != 0.0, axis=(1, 3)).astype(jnp.int32)


def _full(occ: jax.Array, rm: int, rn: int) -> jax.Array:
    """1 where every tile of an (rm, rn)-tile block of ``occ`` is occupied."""
    m, n = occ.shape
    return jnp.all(occ.reshape(m // rm, rm, n // rn, rn) != 0, axis=(1, 3)).astype(jnp.int32)


def _block_work(x_occ: jax.Array, w_occ: jax.Array, rm: int, rn: int,
                rk: int) -> jax.Array:
    """(MI, NJ, KK) bool: True where the kernel's block grid step (I, J, K)
    issues a dot, i.e. some 128-sub-tile has ``x_occ & w_occ``."""
    (mi, kk), nj = x_occ.shape, w_occ.shape[1]
    x_any = jnp.any(x_occ.reshape(mi // rm, rm, kk // rk, rk) != 0, axis=1)
    w_any = jnp.any(w_occ.reshape(kk // rk, rk, nj // rn, rn) != 0, axis=3)
    return jnp.einsum("ikc,kcj->ijk", x_any.astype(jnp.float32),
                      w_any.astype(jnp.float32)) > 0


def _fetch(work: jax.Array) -> jax.Array:
    """Int32 per grid step in row-major (I, J, K) order: the
    :func:`fetch_bits`-packed (I', J', K') of the last working step at or
    before it, else of the first working step ((0, 0, 0) if none works).
    An idle step thus names the blocks its predecessor holds.  Packing
    keeps row-major order, so a running max finds the last working step."""
    _, nj, kk = work.shape
    jb, kb = fetch_bits(nj, kk)
    i, j, k = (jax.lax.broadcasted_iota(jnp.int32, work.shape, d) for d in range(3))
    code = ((i << (jb + kb)) | (j << kb) | k).reshape(-1)
    flat = work.reshape(-1)
    last = jax.lax.cummax(jnp.where(flat, code, -1))
    return jnp.where(last >= 0, last, code[jnp.argmax(flat)])


def prepare(x: jax.Array, w: jax.Array):
    """The pre-compute sparsity stage of ``x @ w``: both operands padded to
    MXU tiles in float32, their occupancy tables ``x_occ`` (Mi, Kk) and
    ``w_occ`` (Kk, Nj), int32 (1 where a tile holds a non-zero), the
    kernel's block tables ``x_full`` (MI, KK) and ``w_full`` (KK, NJ),
    int32 (1 where every tile of a :func:`block_dims` block is occupied),
    and ``fetch`` (MI * NJ * KK,), int32, the packed grid step whose
    operand blocks each step loads (:func:`_fetch`)."""
    with jax.named_scope("spring_mm_prep"):
        m, k = x.shape
        _, n = w.shape
        m_pad, n_pad, k_pad = padded_dims(m, n, k)
        bm, bn, bk = block_dims(m_pad, n_pad, k_pad)
        xp = jnp.pad(x.astype(jnp.float32), ((0, m_pad - m), (0, k_pad - k)))
        wp = jnp.pad(w.astype(jnp.float32), ((0, k_pad - k), (0, n_pad - n)))
        x_occ, w_occ = _occupancy(xp, TILE, TILE), _occupancy(wp, TILE, TILE)
        rm, rn, rk = bm // TILE, bn // TILE, bk // TILE
        work = _block_work(x_occ, w_occ, rm, rn, rk)
        return (xp, wp, x_occ, w_occ, _full(x_occ, rm, rk), _full(w_occ, rk, rn),
                _fetch(work))


def _pair_count(a_table: jax.Array, b_table: jax.Array) -> jax.Array:
    """``[count, total]`` of the (i, j, k) with ``a[i, k] AND b[k, j]``."""
    hits = jnp.einsum("ik,kj->", a_table.astype(jnp.float32), b_table.astype(jnp.float32))
    total = a_table.shape[0] * b_table.shape[0] * b_table.shape[1]
    return jnp.stack([hits, jnp.float32(total)])


def tile_counts(x: jax.Array, w: jax.Array) -> jax.Array:
    """``[issued, total]`` 128-tile MXU steps of ``x @ w`` as float32: a
    tile step (i, j, k) is issued when ``x_occ[i, k] AND w_occ[k, j]``;
    padding tiles count.  Built from :func:`prepare`, so inside a jitted
    program the tables are the ones the kernel's wrapper builds (XLA
    merges the two identical computations)."""
    _, _, x_occ, w_occ, _, _, _ = prepare(x, w)
    with jax.named_scope("spring_mm_prep"):
        return _pair_count(x_occ, w_occ)


def block_counts(x: jax.Array, w: jax.Array) -> jax.Array:
    """``[one_dot, total, idle]`` block grid steps of the kernel on ``x @ w``
    as float32: a block step (I, J, K) takes the one-dot path when
    ``x_full[I, K] AND w_full[K, J]``, and is idle (issues no dot, so its
    operand copies are elided) where :func:`_block_work` is False, from
    :func:`prepare`'s tables."""
    _, _, x_occ, w_occ, x_full, w_full, _ = prepare(x, w)
    with jax.named_scope("spring_mm_prep"):
        rm, rk = x_occ.shape[0] // x_full.shape[0], x_occ.shape[1] // x_full.shape[1]
        rn = w_occ.shape[1] // w_full.shape[1]
        work = _block_work(x_occ, w_occ, rm, rn, rk)
        idle = jnp.float32(work.size) - jnp.sum(work, dtype=jnp.float32)
        return jnp.concatenate([_pair_count(x_full, w_full), idle[None]])


def call_counts(x: jax.Array, w: jax.Array) -> jax.Array:
    """``tile_counts`` then ``block_counts`` of one kernel call ``x @ w``."""
    return jnp.concatenate([tile_counts(x, w), block_counts(x, w)])


def probe_counts(fwd: jax.Array, dx: jax.Array, dw: jax.Array) -> jax.Array:
    """The tile probe's cotangent from the :func:`call_counts` of one
    ``x @ w``'s forward, dx and dw calls: ``[fwd_issued, fwd_total,
    dx_issued, dx_total, dw_issued, dw_total, one_dot_blocks, blocks,
    idle_blocks]``, the block counts summed over the three calls."""
    return jnp.concatenate([fwd[:2], dx[:2], dw[:2], fwd[2:] + dx[2:] + dw[2:]])


@partial(jax.jit, static_argnames=("il", "fl", "apply_sr"))
def _mm_ref(x, w, seed, *, il=4, fl=16, apply_sr=True):
    return masked_matmul_reference(x, w, seed, il=il, fl=fl, apply_sr=apply_sr)


@partial(jax.jit, static_argnames=("il", "fl", "apply_sr", "interpret"))
def _mm_kernel(x, w, seed, *, il=4, fl=16, apply_sr=True, interpret=False):
    out = masked_matmul_pallas(
        *prepare(x, w), seed,
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
    # blocks (bm, bn, bk): 128^3 (M pads to 128), 128 x 256 x 512 (N pads
    # 200 -> 256), 512 x 512 x 384, and 384 x 384 x 512 over two k-steps
    for m, k, n in [(128, 128, 128), (100, 70, 50), (64, 512, 200),
                    (512, 384, 512), (384, 1024, 384)]:
        x = _example_operands(m * 7 + k, (m, k))
        w = _example_operands(n * 13 + k, (k, n))
        cases.append(((x, w, jnp.uint32(5)), {}))
    # block-pruned operands: one 256 x 256 x 384 block whose empty tiles
    # the sub-tile path skips, plus the SR-off path
    x = _example_operands(0, (256, 384), 0.3).at[:128, :256].set(0.0)
    w = _example_operands(1, (384, 256), 0.3).at[256:, 128:].set(0.0)
    cases.append(((x, w, jnp.uint32(3)), {}))
    cases.append(((x, w, jnp.uint32(3)), {"apply_sr": False},
                  {"kind": "allclose", "atol": 1e-6, "rtol": 0.0}))
    # 512-row blocks: one all-empty beside one on the one-dot path
    x = _example_operands(2, (1024, 256), 0.3).at[:512].set(0.0)
    w = _example_operands(3, (256, 256), 0.3)
    cases.append(((x, w, jnp.uint32(7)), {}))
    cases.append(((x, w, jnp.uint32(7)), {"apply_sr": False},
                  {"kind": "allclose", "atol": 1e-6, "rtol": 0.0}))
    # idle block steps load the previous step's blocks: live rows in row
    # blocks 1 and 3 of five 256-row blocks (leading, interior and
    # trailing idle runs, over two k-steps), and an all-empty operand
    x = _example_operands(4, (1280, 768), 0.3)
    for lo, hi in ((0, 256), (320, 768), (896, 1280)):
        x = x.at[lo:hi].set(0.0)
    cases.append(((x, _example_operands(5, (768, 384), 0.3), jnp.uint32(9)), {}))
    cases.append(((jnp.zeros((1024, 256)), _example_operands(6, (256, 384), 0.3),
                   jnp.uint32(11)), {}))
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
    """Fraction of (i,j,k) 128-tile MXU steps skipped for these operands.

    The roofline compute-term scales by (1 - skip_fraction) on TPU; this
    is the analytically-reportable speedup of the kernel (§Perf).
    """
    issued, total = tile_counts(x, w)
    return 1.0 - issued / total
