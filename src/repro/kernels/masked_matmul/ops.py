"""The sparsity-aware fixed-point matmul: the one owner of how ``x @ w`` is
lowered and differentiated.

Handles padding to MXU tiles, occupancy-mask computation (the packed
binary masks AND-reduced per tile — SPRING's pre-compute sparsity stage,
:func:`prepare`), and registers the forward's implementations with
``repro.kernels.registry``:

  ref        dense f32 matmul + identical SR epilogue (vectorized oracle;
             the CPU production path)
  interpret  the Pallas kernel in interpret mode (tests)
  pallas     the Pallas kernel (TPU)

Every call goes through one ``jax.custom_vjp`` (:func:`masked_matmul`)
whose forward, dx and dw lowerings (``masked_matmul``,
``masked_matmul_dx``, ``masked_matmul_dw``, the last two registered in
``backward.py``) are resolved once, at the call; a lowering is pinned only
through the ``KernelPolicy``.  Every lowering returns its product and
the :func:`call_counts` of the kernel call; a kernel lowering builds one
:class:`MmPlan` inside its jit, which both its kernel and its counts read.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import registry
from repro.kernels.masked_matmul.mm_kernel import (
    TILE, block_dims, fetch_bits, masked_matmul_pallas, padded_dims)
from repro.kernels.masked_matmul.ref import masked_matmul_reference

#: Length of the tile probe: the ``[issued, total]`` 128-tile steps of
#: the forward, dx and dw calls, in that order, then their ``[one_dot,
#: total, idle]`` block grid steps summed (see :func:`probe_counts`).
PROBE_SIZE = 9


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


class MmPlan(NamedTuple):
    """The pre-compute sparsity stage of one product ``x @ w``."""

    x: jax.Array       # (M_pad, K_pad) float32, ``x`` padded to MXU tiles
    w: jax.Array       # (K_pad, N_pad) float32
    x_occ: jax.Array   # (Mi, Kk) int32: 1 where a 128-tile holds a non-zero
    w_occ: jax.Array   # (Kk, Nj) int32
    x_full: jax.Array  # (MI, KK) int32: 1 where every tile of a block is occupied
    w_full: jax.Array  # (KK, NJ) int32, blocks of :func:`block_dims`
    work: jax.Array    # (MI, NJ, KK) bool: the block grid steps that issue a dot
    fetch: jax.Array   # (MI * NJ * KK,) int32: the step whose blocks each step loads


def prepare(x: jax.Array, w: jax.Array) -> MmPlan:
    """The :class:`MmPlan` of ``x @ w``; every op inside ``spring_mm_prep``."""
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
        return MmPlan(xp, wp, x_occ, w_occ, _full(x_occ, rm, rk), _full(w_occ, rk, rn),
                      work, _fetch(work))


def _pair_count(a_table: jax.Array, b_table: jax.Array) -> jax.Array:
    """``[count, total]`` of the (i, j, k) with ``a[i, k] AND b[k, j]``."""
    hits = jnp.einsum("ik,kj->", a_table.astype(jnp.float32), b_table.astype(jnp.float32))
    total = a_table.shape[0] * b_table.shape[0] * b_table.shape[1]
    return jnp.stack([hits, jnp.float32(total)])


def tile_counts(plan: MmPlan) -> jax.Array:
    """``[issued, total]`` 128-tile MXU steps of the planned product as
    float32: a tile step (i, j, k) is issued when ``x_occ[i, k] AND
    w_occ[k, j]``; padding tiles count."""
    with jax.named_scope("spring_mm_prep"):
        return _pair_count(plan.x_occ, plan.w_occ)


def block_counts(plan: MmPlan) -> jax.Array:
    """``[one_dot, total, idle]`` block grid steps of the kernel on the
    planned product as float32: a block step (I, J, K) takes the one-dot
    path when ``x_full[I, K] AND w_full[K, J]``, and is idle (issues no
    dot, so its operand copies are elided) where ``work`` is False."""
    with jax.named_scope("spring_mm_prep"):
        idle = jnp.float32(plan.work.size) - jnp.sum(plan.work, dtype=jnp.float32)
        return jnp.concatenate([_pair_count(plan.x_full, plan.w_full), idle[None]])


def call_counts(plan: MmPlan) -> jax.Array:
    """``tile_counts`` then ``block_counts`` of one kernel call."""
    return jnp.concatenate([tile_counts(plan), block_counts(plan)])


def probe_counts(fwd: jax.Array, dx: jax.Array, dw: jax.Array) -> jax.Array:
    """The tile probe's cotangent from the :func:`call_counts` of one
    ``x @ w``'s forward, dx and dw calls: ``[fwd_issued, fwd_total,
    dx_issued, dx_total, dw_issued, dw_total, one_dot_blocks, blocks,
    idle_blocks]``, the block counts summed over the three calls."""
    return jnp.concatenate([fwd[:2], dx[:2], dw[:2], fwd[2:] + dx[2:] + dw[2:]])


@partial(jax.jit, static_argnames=("il", "fl", "apply_sr"))
def _mm_ref(x, w, seed, *, il=4, fl=16, apply_sr=True):
    return (masked_matmul_reference(x, w, seed, il=il, fl=fl, apply_sr=apply_sr),
            call_counts(prepare(x, w)))


# The device op of every pallas_call takes the name of the jitted function
# around it: this one, ``_mm_kernel`` (the benchmark reads kernel time by it).
@partial(jax.jit, static_argnames=("il", "fl", "apply_sr", "interpret"))
def _mm_kernel(x, w, seed, *, il=4, fl=16, apply_sr=True, interpret=False):
    plan = prepare(x, w)
    out = masked_matmul_pallas(plan, seed, il=il, fl=fl, apply_sr=apply_sr,
                               interpret=interpret)
    return out[:x.shape[0], :w.shape[1]], call_counts(plan)


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


# ---------------------------------------------------------------------------
# The one custom_vjp.
# ---------------------------------------------------------------------------


def _run(lowering, *args, **kwargs):
    """One product through ``lowering``: its result and :func:`call_counts`;
    the tile-skip fraction is recorded under the lowering's op where
    instrumentation is on and the call is eager."""
    y, counts = lowering.fn(*args, **kwargs)
    if registry.metrics_active() and not isinstance(counts, jax.core.Tracer):
        registry.note_metric(lowering.op, tile_skip=float(1.0 - counts[0] / counts[1]))
    return y, counts


def backward_lowerings(policy=None) -> tuple:
    """The ``masked_matmul_dx`` and ``masked_matmul_dw`` lowerings, each
    resolved once under ``policy`` (a ``SpringConfig.kernels``; an auto
    policy defers to the ambient one).  Their dispatch is counted where
    they run (:func:`grad_product`)."""
    with registry.policy_scope(policy):
        return (registry.resolve("masked_matmul_dx", _count=False),
                registry.resolve("masked_matmul_dw", _count=False))


def _lowerings(policy=None, impl=None) -> tuple:
    """The forward (``impl`` pins it), dx and dw lowerings of one call."""
    with registry.policy_scope(policy):
        return (registry.resolve("masked_matmul", impl),) + backward_lowerings()


def grad_product(lowering, lhs: jax.Array, rhs: jax.Array):
    """One backward product through a resolved ``masked_matmul_dx``
    lowering (on ``(g, w)``: ``g @ w.T``) or ``masked_matmul_dw`` lowering
    (on ``(x, g)``: ``x.T @ g``): its result and :func:`call_counts`."""
    registry.record_dispatch(lowering.op, lowering.name)
    return _run(lowering, lhs, rhs)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _mm(x, w, seed, probe, il, fl, apply_sr, lowerings):
    del probe
    return _run(lowerings[0], x, w, seed, il=il, fl=fl, apply_sr=apply_sr)[0]


def _mm_fwd(x, w, seed, probe, il, fl, apply_sr, lowerings):
    del probe
    y, counts = _run(lowerings[0], x, w, seed, il=il, fl=fl, apply_sr=apply_sr)
    # Residual: the (sparse) operands only — never the dense accumulator —
    # plus the forward's tile and block counts.  The SR epilogue is
    # straight-through in the backward (DESIGN.md §8): range clipping is
    # handled by the caller's STE quantizer, keeping the residual at
    # exactly what SPRING's stash stores.
    return y, (x, w, seed, counts)


def _mm_bwd(il, fl, apply_sr, lowerings, res, g):
    x, w, seed, fwd_counts = res
    dx, dx_counts = grad_product(lowerings[1], g, w)
    dw, dw_counts = grad_product(lowerings[2], x, g)
    # the probe's cotangent carries this call's tile counts out of the
    # backward pass; autodiff sums it over every call of the step
    return (dx, dw, np.zeros(np.shape(seed), dtype=jax.dtypes.float0),
            probe_counts(fwd_counts, dx_counts, dw_counts))


_mm.defvjp(_mm_fwd, _mm_bwd)


def _call(x, w, seed, lowerings, probe, *, il, fl, apply_sr):
    if probe is None:
        probe = jnp.zeros((PROBE_SIZE,), jnp.float32)
    return _mm(x, w, seed, probe, il, fl, apply_sr, lowerings)


def masked_matmul(
    x: jax.Array,
    w: jax.Array,
    seed: jax.Array | None = None,
    *,
    il: int = 4,
    fl: int = 16,
    apply_sr: bool = True,
    impl: str | None = None,
    probe: jax.Array | None = None,
) -> jax.Array:
    """Sparsity-aware ``x @ w`` on the Q(il,fl) grid with SR epilogue.

    x: (M, K) float32 grid values (zeros = skippable); w: (K, N).
    ``impl`` pins the forward's implementation; None defers to the active
    :class:`~repro.kernels.registry.KernelPolicy`, which also picks dL/dx
    and dL/dw (``masked_matmul_dx`` / ``masked_matmul_dw``): tile
    skipping applies in both directions (DESIGN.md §8).

    ``probe`` is the tile counter: a float32 vector of :data:`PROBE_SIZE`
    that the result does not depend on; its gradient is this call's
    ``[fwd_issued, fwd_total, dx_issued, dx_total, dw_issued, dw_total,
    one_dot_blocks, blocks, idle_blocks]`` (see :func:`probe_counts`).
    Differentiating a whole program with respect to one probe shared by
    every call sums them, under ``scan``, remat and ``jit`` alike.
    """
    seed = jnp.uint32(0) if seed is None else seed
    return _call(x, w, seed, _lowerings(impl=impl), probe, il=il, fl=fl,
                 apply_sr=apply_sr)


def quant_sparse_matmul(x: jax.Array, w: jax.Array, policy=None,
                        probe: jax.Array | None = None) -> jax.Array:
    """``spring_matmul``'s quant_sparse product of 2-D grid values: the
    one custom_vjp, with the forward, dx and dw lowerings each resolved
    once under ``policy`` (a ``SpringConfig.kernels``).

    The SR rule, here alone: a kernel lowering (``interpret``, ``pallas``)
    fuses the SR epilogue at Q(4, 16) with seed 0, so every call and step
    draws the same uniform per position and the caller's own rounding is
    an identity on the grid values it returns; the ``ref`` lowering
    returns the float32 product and leaves SR to the caller.  Passing the
    caller's key here is ROADMAP D10.
    """
    lowerings = _lowerings(policy)
    fused = lowerings[0].name in ("interpret", "pallas")
    return _call(x, w, jnp.uint32(0), lowerings, probe, il=4, fl=16, apply_sr=fused)


def tile_skip_fraction(x: jax.Array, w: jax.Array) -> jax.Array:
    """Fraction of (i,j,k) 128-tile MXU steps skipped for these operands.

    The roofline compute-term scales by (1 - skip_fraction) on TPU; this
    is the analytically-reportable speedup of the kernel (§Perf).
    """
    issued, total = tile_counts(prepare(x, w))
    return 1.0 - issued / total
