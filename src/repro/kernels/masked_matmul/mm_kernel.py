"""Pallas TPU kernel: sparsity-aware fixed-point matmul with SR epilogue.

This is the MXU-granular realization of SPRING's pre-compute sparsity
module + MAC lanes (paper Figs. 6-8, DESIGN.md §2/P1):

  * Operands are Q(IL,FL) grid values, padded to 128-multiples.  Per-
    (128x128)-tile *occupancy masks* (the AND-reduction of SPRING's
    element binary masks over a tile) are computed outside and
    prefetched into SMEM as flat scalar tables, one entry per tile,
    beside per-block *full* tables (1 where every 128-tile of the block
    is occupied).
  * The grid walks (M/bm, N/bn, K/bk) blocks of 128-tiles, with
    ``(bm, bn, bk) = block_dims(...)`` chosen from the padded shape
    alone.  A block step whose x and w blocks are both full issues one
    MXU matmul over the whole block; any other block step loops over its
    128-sub-tiles and issues a sub-tile only when
    ``x_occ[i,k] AND w_occ[k,j]`` — the AND-mask gate of Fig. 7(a)
    lifted to tile granularity.  All-zero tiles cost no MXU work
    ("ineffectual computations are completely skipped"), exactly the
    tiles a grid of single 128-tiles would skip.
  * An *idle* block step, one whose sub-tiles all fail the gate, costs
    no copies either: a prefetched ``fetch`` table points its x and w
    index maps at the blocks the previous step already holds, and the
    Pallas pipeline issues no DMA for a block index that does not
    change.  The body never reads an operand on an idle step (it skips
    the sub-tile loop where ``fetch`` names another step), so the
    redirected blocks do not touch the result.  Where every step works,
    ``fetch`` is the identity and the copies are the plain grid's.
  * The epilogue applies stochastic rounding (paper Eq. 4) back to
    Q(IL,FL) using the same counter-based xorshift stream as
    ``kernels/stochastic_round``; the counter is the element's position
    in the 128-padded output, whatever the block.

Numerics note: skipping a tile whose joint occupancy is empty adds
exactly 0.0 to the f32 accumulator, so outputs are bit-identical to the
dense evaluation of the same (masked) operands — SPRING's dangling
non-zeros never influence results, they only waste work when not skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.prng import hash_uint32, uniform_from_bits

#: The skip gate's granularity: one MXU tile.  Operands pad to it.
TILE = 128
#: Block sides a grid step may take, largest first: each a multiple of
#: TILE, the largest keeping the double-buffered f32 blocks near 6 MiB.
BLOCKS = (512, 384, 256, 128)


def padded_dims(m: int, n: int, k: int) -> tuple[int, int, int]:
    return (pl.cdiv(m, TILE) * TILE, pl.cdiv(n, TILE) * TILE, pl.cdiv(k, TILE) * TILE)


def block_dims(m_pad: int, n_pad: int, k_pad: int) -> tuple[int, int, int]:
    """``(bm, bn, bk)`` of the kernel's grid for 128-padded dims: each the
    largest of :data:`BLOCKS` that divides its dim."""
    return tuple(next(b for b in BLOCKS if d % b == 0) for d in (m_pad, n_pad, k_pad))


def fetch_bits(n_blocks: int, k_steps: int) -> tuple[int, int]:
    """Bits of the J and K fields of a packed grid step ``(I << (jb + kb))
    | (J << kb) | K`` on a grid of ``n_blocks`` x ``k_steps``: the index
    maps unpack it with shifts and masks, no division."""
    return (n_blocks - 1).bit_length(), (k_steps - 1).bit_length()


def _mm_kernel(
    xo_ref,
    wo_ref,
    xf_ref,
    wf_ref,
    seed_ref,
    fetch_ref,
    x_ref,
    w_ref,
    out_ref,
    *,
    k_steps: int,
    n_blocks: int,
    j_bits: int,
    k_bits: int,
    k_tiles: int,
    n_tiles: int,
    n_pad: int,
    fl: int,
    min_v: float,
    max_v: float,
    apply_sr: bool,
):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    (bm, bk), bn = x_ref.shape, w_ref.shape[1]
    rm, rn, rk = bm // TILE, bn // TILE, bk // TILE

    @pl.when(k == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    full = (xf_ref[i * k_steps + k] & wf_ref[k * n_blocks + j]) != 0
    # a step whose fetch names another step's blocks is idle: no sub-tile
    # passes the gate, so its loop is skipped too
    own = (i << (j_bits + k_bits)) | (j << k_bits) | k
    works = fetch_ref[(i * n_blocks + j) * k_steps + k] == own

    @pl.when(full)
    def _block():
        out_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32
        )

    if rm * rn * rk > 1:  # a one-tile block is full exactly when occupied

        @pl.when(jnp.logical_not(full) & works)
        def _tiles():
            def sub_tile(t, carry):
                a, b, c = t // (rn * rk), (t // rk) % rn, t % rk
                ti, tj, tk = i * rm + a, j * rn + b, k * rk + c

                @pl.when((xo_ref[ti * k_tiles + tk] & wo_ref[tk * n_tiles + tj]) != 0)
                def _mac():
                    rows = pl.ds(pl.multiple_of(a * TILE, TILE), TILE)
                    cols = pl.ds(pl.multiple_of(b * TILE, TILE), TILE)
                    inner = pl.ds(pl.multiple_of(c * TILE, TILE), TILE)
                    out_ref[rows, cols] += jnp.dot(
                        x_ref[rows, inner], w_ref[inner, cols],
                        preferred_element_type=jnp.float32,
                    )

                return carry

            jax.lax.fori_loop(0, rm * rn * rk, sub_tile, 0)

    if apply_sr:

        @pl.when(k == k_steps - 1)
        def _epilogue():
            acc = out_ref[...]
            scale = jnp.float32(2.0**fl)
            xc = jnp.clip(acc, min_v, max_v)
            scaled = xc * scale
            lo = jnp.floor(scaled)
            frac = scaled - lo
            rows = jax.lax.broadcasted_iota(jnp.uint32, acc.shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.uint32, acc.shape, 1)
            gi = jnp.uint32(i) * jnp.uint32(bm) + rows
            gj = jnp.uint32(j) * jnp.uint32(bn) + cols
            counter = gi * jnp.uint32(n_pad) + gj
            u = uniform_from_bits(hash_uint32(counter, seed_ref[0]))
            rounded = lo + (u < frac).astype(jnp.float32)
            out_ref[...] = jnp.clip(rounded * jnp.float32(2.0**-fl), min_v, max_v)


def masked_matmul_pallas(plan, seed: jax.Array, *, il: int = 4, fl: int = 16,
                         apply_sr: bool = True, interpret: bool = False) -> jax.Array:
    """(M,K) @ (K,N) with tile skipping, from the ``ops.MmPlan`` of the
    product: its TILE-padded operands ``x`` and ``w``, and its tables.

    x_occ: (M/TILE, K/TILE) and w_occ: (K/TILE, N/TILE) int32 per tile;
    x_full: (M/bm, K/bk) and w_full: (K/bk, N/bn) int32 per block of
    :func:`block_dims`; fetch: int32 per grid step, row-major over
    (M/bm, N/bn, K/bk), the :func:`fetch_bits`-packed step whose x and w
    blocks that step loads (the step itself where it issues a dot, else a
    step whose blocks the previous step already holds, so an idle step
    copies nothing).  The five tables and the seed are scalar-prefetched
    into SMEM (flattened row-major).
    """
    x, w, x_occ, w_occ, x_full, w_full, fetch = (
        plan.x, plan.w, plan.x_occ, plan.w_occ, plan.x_full, plan.w_full, plan.fetch)
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and m % TILE == 0 and n % TILE == 0 and k % TILE == 0
    bm, bn, bk = block_dims(m, n, k)
    grid = (m // bm, n // bn, k // bk)
    assert x_full.shape == (grid[0], grid[2]) and w_full.shape == (grid[2], grid[1])
    assert fetch.size == grid[0] * grid[1] * grid[2]
    nj, kk = grid[1], grid[2]
    jb, kb = fetch_bits(nj, kk)
    assert (grid[0] - 1).bit_length() + jb + kb <= 31

    def fetched(i, j, k, *tables):
        """(I', J', K') of the blocks grid step (i, j, k) loads."""
        f = tables[5][(i * nj + j) * kk + k]
        return f >> (jb + kb), (f >> kb) & ((1 << jb) - 1), f & ((1 << kb) - 1)

    def x_map(i, j, k, *tables):
        fi, _, fk = fetched(i, j, k, *tables)
        return fi, fk

    def w_map(i, j, k, *tables):
        _, fj, fk = fetched(i, j, k, *tables)
        return fk, fj

    eps = 2.0**-fl
    kernel = functools.partial(
        _mm_kernel,
        k_steps=grid[2],
        n_blocks=grid[1],
        j_bits=jb,
        k_bits=kb,
        k_tiles=k // TILE,
        n_tiles=n // TILE,
        n_pad=n,
        fl=fl,
        min_v=-(2.0**il),
        max_v=2.0**il - eps,
        apply_sr=apply_sr,
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), x_map), pl.BlockSpec((bk, bn), w_map)],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(
        x_occ.astype(jnp.int32).reshape(-1),
        w_occ.astype(jnp.int32).reshape(-1),
        x_full.astype(jnp.int32).reshape(-1),
        w_full.astype(jnp.int32).reshape(-1),
        seed.astype(jnp.uint32).reshape(1),
        fetch.astype(jnp.int32).reshape(-1),
        x,
        w,
    )
