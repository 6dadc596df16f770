"""Pallas TPU kernels for SPRING's binary-mask machinery (paper Figs. 5-7).

Two kernels:

  * ``mask_pack``: dense f32 block -> packed uint32 mask words (1 bit per
    element, 32 per word — the Fig. 5 storage format).  Realized as two
    exact 0/1 x power-of-two matmuls on the MXU, one per half word.
  * ``dangling_filter``: the pre-compute sparsity module's mask generation
    + dangling-data filter (Figs. 7a/7b) on dense-layout operand tiles:
    joint = (a != 0) & (w != 0); each operand keeps only joint survivors.

The zero-collapsing shifter (Fig. 7c) is a data-dependent compaction; on
TPU that is a cumsum+scatter which XLA already emits well, so it stays in
``core/masking.py`` (DESIGN.md §2/P1).  The element-serial Algorithm 1 is
the oracle in ``ref.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8
COLS = 1024  # lanes; must be a multiple of 32
WORDS = COLS // 32


def _half_word_weights(half: int) -> jax.Array:
    """(COLS, WORDS) f32: lane c feeds word c // 32 with weight
    2**(c % 32 - 16 * half) when bit c % 32 lies in 16-bit ``half``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (COLS, WORDS), 0)
    word = jax.lax.broadcasted_iota(jnp.int32, (COLS, WORDS), 1)
    bit = lane & 31
    hit = ((lane >> 5) == word) & ((bit >> 4) == half)
    weight = jnp.left_shift(jnp.int32(1), bit & 15).astype(jnp.float32)
    return jnp.where(hit, weight, 0.0)


def _pack_kernel(x_ref, out_ref):
    # The shift-and-OR over each 32-lane group runs as two matmuls on the
    # MXU, one per 16-bit half word: the bits are 0/1 and the weights are
    # powers of two below 2**16, so every product and f32 partial sum is
    # exact.  (Mosaic has no unsigned reductions and no lane-splitting
    # reshape, which the plain shift-and-sum would need.)
    bits = (x_ref[...] != 0.0).astype(jnp.float32)  # (ROWS, COLS)
    lo, hi = (
        jnp.dot(bits, _half_word_weights(half),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
        for half in (0, 1)
    )
    words = lo | (hi << 16)
    out_ref[...] = jax.lax.bitcast_convert_type(words, jnp.uint32)


def mask_pack_pallas(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    """(R, COLS) f32 -> (R, COLS/32) uint32 packed occupancy mask."""
    r, c = x.shape
    assert c == COLS and r % ROWS == 0, (x.shape,)
    return pl.pallas_call(
        _pack_kernel,
        grid=(r // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, COLS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, WORDS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, WORDS), jnp.uint32),
        interpret=interpret,
    )(x.astype(jnp.float32))


def _filter_kernel(a_ref, w_ref, a_out_ref, w_out_ref):
    a = a_ref[...]
    w = w_ref[...]
    joint = (a != 0.0) & (w != 0.0)  # Fig. 7(a): AND of the binary masks
    a_out_ref[...] = jnp.where(joint, a, 0.0)  # Fig. 7(b): dangling filtered
    w_out_ref[...] = jnp.where(joint, w, 0.0)


def dangling_filter_pallas(
    a: jax.Array, w: jax.Array, *, interpret: bool = False
) -> tuple[jax.Array, jax.Array]:
    """Dense-layout pre-compute sparsity filter on (R, COLS) operand tiles."""
    r, c = a.shape
    assert a.shape == w.shape and c == COLS and r % ROWS == 0
    return pl.pallas_call(
        _filter_kernel,
        grid=(r // ROWS,),
        in_specs=[
            pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), jnp.float32),
            jax.ShapeDtypeStruct((r, c), jnp.float32),
        ],
        interpret=interpret,
    )(a.astype(jnp.float32), w.astype(jnp.float32))
