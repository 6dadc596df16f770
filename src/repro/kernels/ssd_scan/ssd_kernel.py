"""Pallas TPU kernel: Mamba-2 SSD (state-space duality) chunked scan.

Assigned architecture ``mamba2-780m`` [arXiv:2405.21060].  The SSD
recurrence per head (state N, head dim P):

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t x_t^T        (N x P)
    y_t = C_t @ h_t

is evaluated chunkwise: within a length-L chunk the lower-triangular
decay-weighted score matrix turns the recurrence into two MXU matmuls
(the "duality"); across chunks a single (N, P) state carries in VMEM
scratch along the sequential grid axis.

Grid: (B, H, S/L) with the chunk axis sequential.  B/C are grouped
(G state-groups, GQA-style): head h reads group h // (H/G) via the
index map, so grouped B/C are never materialized per head.

Layout: the wrapper moves the head axis in front of the sequence axis so
every block's last two dims are (chunk, feature) and satisfy the TPU
(8, 128) tiling rule.  The per-step decay terms (chunk-local cumulative
log decay ``cum`` and ``dt``) are cheap elementwise work that the wrapper
computes in XLA and hands in as a column (L, 1) and rows (1, L), so the
kernel is matmuls, broadcasts and the carried state only.  B enters
transposed, (N, L), so both contractions are plain row-major matmuls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128


def _ssd_kernel(x_ref, cum_col_ref, cum_row_ref, dt_row_ref, bt_ref, c_ref,
                y_ref, state_scr):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)  # (L, P)
    cum_col = cum_col_ref[0, 0]  # (L, 1) inclusive cumsum of dt * a
    cum_row = cum_row_ref[0, 0]  # (1, L)
    dt_row = dt_row_ref[0, 0]  # (1, L)
    bt = bt_ref[0, 0].astype(jnp.float32)  # (N, L)
    cm = c_ref[0, 0].astype(jnp.float32)  # (L, N)
    l = x.shape[0]

    # Intra-chunk (the dual quadratic form): S[t, j] = (C_t . B_j)
    #   * exp(cum[t] - cum[j]) * dt[j], masked to j <= t.
    scores = jnp.dot(cm, bt, preferred_element_type=jnp.float32)  # (L, L)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    # Mask the exponent: the upper triangle has positive diffs that would
    # overflow exp to inf (exp(-inf) = 0 is the safe form).
    diff = jnp.where(t_idx >= j_idx, cum_col - cum_row, -jnp.inf)
    w = jnp.exp(diff)
    y_intra = jnp.dot(scores * w * dt_row, x, preferred_element_type=jnp.float32)

    # Inter-chunk: contribution of the carried state.
    h0 = state_scr[...]  # (N, P)
    y_inter = jnp.exp(cum_col) * jnp.dot(cm, h0, preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # State for the next chunk.
    cum_end = cum_row[:, l - 1:]  # (1, 1)
    decay_dt = jnp.exp(cum_end - cum_row) * dt_row  # (1, L)
    state_scr[...] = jnp.exp(cum_end) * h0 + jnp.dot(
        bt * decay_dt, x, preferred_element_type=jnp.float32
    )


def ssd_scan_pallas(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """x: (B,S,H,P); dt: (B,S,H); a: (H,) negative; b/c: (B,S,G,N).

    S must be a multiple of CHUNK (wrapper pads).  Returns y: (B,S,H,P).
    """
    bsz, s, h, p = x.shape
    _, _, g, n = b.shape
    assert s % CHUNK == 0 and h % g == 0
    group = h // g
    nc = s // CHUNK
    grid = (bsz, h, nc)

    dtf = jnp.moveaxis(dt.astype(jnp.float32), 2, 1)  # (B,H,S)
    da = (dtf * a.astype(jnp.float32)[None, :, None]).reshape(bsz, h, nc, CHUNK)
    cum = jnp.cumsum(da, axis=-1).reshape(bsz, h, s)
    xh = jnp.moveaxis(x, 2, 1)  # (B,H,S,P)
    bt = jnp.moveaxis(b, 1, 3)  # (B,G,N,S)
    cg = jnp.moveaxis(c, 2, 1)  # (B,G,S,N)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    col = pl.BlockSpec((1, 1, CHUNK, 1), lambda b_, h_, c_: (b_, h_, c_, 0))
    row = pl.BlockSpec((1, 1, 1, CHUNK), lambda b_, h_, c_: (b_, h_, 0, c_))
    y = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, CHUNK, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            col,
            row,
            row,
            pl.BlockSpec((1, 1, n, CHUNK), lambda b_, h_, c_: (b_, h_ // group, 0, c_)),
            pl.BlockSpec((1, 1, CHUNK, n), lambda b_, h_, c_: (b_, h_ // group, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, CHUNK, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(xh, cum[..., None], cum[:, :, None, :], dtf[:, :, None, :], bt, cg)
    return jnp.moveaxis(y, 1, 2)
