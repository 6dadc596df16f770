"""Pallas TPU kernel: sparsity-aware fixed-point matmul with SR epilogue.

This is the MXU-granular realization of SPRING's pre-compute sparsity
module + MAC lanes (paper Figs. 6-8, DESIGN.md §2/P1):

  * Operands are Q(IL,FL) grid values.  Per-(128x128)-tile *occupancy
    masks* (the AND-reduction of SPRING's element binary masks over a
    tile) are computed outside and prefetched into SMEM as flat scalar
    tables, one entry per tile.
  * The grid walks (M/bm, N/bn, K/bk); a k-step issues the MXU matmul
    only when ``x_occ[i,k] AND w_occ[k,j]`` — the AND-mask gate of
    Fig. 7(a) lifted to tile granularity.  All-zero tiles cost no MXU
    work ("ineffectual computations are completely skipped").
  * The epilogue applies stochastic rounding (paper Eq. 4) back to
    Q(IL,FL) using the same counter-based xorshift stream as
    ``kernels/stochastic_round``.

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

BM = 128
BN = 128
BK = 128


def padded_dims(m: int, n: int, k: int) -> tuple[int, int, int]:
    return (pl.cdiv(m, BM) * BM, pl.cdiv(n, BN) * BN, pl.cdiv(k, BK) * BK)


def _mm_kernel(
    xo_ref,
    wo_ref,
    seed_ref,
    x_ref,
    w_ref,
    out_ref,
    *,
    k_steps: int,
    n_tiles: int,
    n_pad: int,
    fl: int,
    min_v: float,
    max_v: float,
    apply_sr: bool,
):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    occupied = (xo_ref[i * k_steps + k] & wo_ref[k * n_tiles + j]) != 0

    @pl.when(occupied)
    def _mac():
        out_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32
        )

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
            gi = jnp.uint32(i) * jnp.uint32(BM) + rows
            gj = jnp.uint32(j) * jnp.uint32(BN) + cols
            counter = gi * jnp.uint32(n_pad) + gj
            u = uniform_from_bits(hash_uint32(counter, seed_ref[0]))
            rounded = lo + (u < frac).astype(jnp.float32)
            out_ref[...] = jnp.clip(rounded * jnp.float32(2.0**-fl), min_v, max_v)


def masked_matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    x_occ: jax.Array,
    w_occ: jax.Array,
    seed: jax.Array,
    *,
    il: int = 4,
    fl: int = 16,
    apply_sr: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """(M,K) @ (K,N) with tile skipping. Inputs must be block-padded.

    x_occ: (M/BM, K/BK) int32; w_occ: (K/BK, N/BN) int32.  Both tables
    and the seed are scalar-prefetched into SMEM (flattened row-major).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and m % BM == 0 and n % BN == 0 and k % BK == 0
    grid = (m // BM, n // BN, k // BK)
    eps = 2.0**-fl
    kernel = functools.partial(
        _mm_kernel,
        k_steps=grid[2],
        n_tiles=grid[1],
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
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j, kk, *_: (i, kk)),
            pl.BlockSpec((BK, BN), lambda i, j, kk, *_: (kk, j)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, kk, *_: (i, j)),
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
        seed.astype(jnp.uint32).reshape(1),
        x,
        w,
    )
