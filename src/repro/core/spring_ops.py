"""Public SPRING compute ops: quantized, sparsity-aware matmul/conv.

Every linear/conv layer in the model zoo funnels through these.  Three
modes (``SpringMode``):

  dense        — plain bf16/fp32 baseline (the 'GPU' reference numerics).
  quant        — Q(IL,FL) fixed-point operands, fp32 accumulate, stochastic
                 rounding on the output (paper P2; training-safe via STE).
  quant_sparse — quant + binary-mask sparsity: dangling non-zeros are
                 filtered (numerics identical to quant with masked
                 operands) and, on TPU, all-zero MXU tiles are skipped by
                 the ``masked_matmul`` Pallas kernel (paper P1).

How a quant_sparse product is lowered and differentiated belongs to
``kernels/masked_matmul`` alone: ``spring_matmul`` hands it the operands
and ``SpringConfig.kernels``, under which it resolves its forward, dx and
dw lowerings (auto picks Pallas on TPU, the ``ref`` lowering elsewhere).
"""

from __future__ import annotations

import dataclasses
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.core.fixedpoint import (
    SPRING_FORMAT,
    FixedPointFormat,
    ste_quantize_nearest,
    ste_quantize_stochastic,
)
from repro.kernels.registry import KernelPolicy
from repro.kernels.masked_matmul import ops as mm_ops

SpringMode = Literal["dense", "quant", "quant_sparse"]

#: Backward-sparsity switch values: "none" takes the plain float32 product
#: and JAX autodiff; "auto" routes the product through masked_matmul's
#: custom_vjp, whose dL/dX / dL/dW are the masked_matmul_dx/dw lowerings
#: (pinned, like the forward, only through ``kernels``).
BACKWARD_SPARSITY_CHOICES = ("none", "auto")


@dataclasses.dataclass(frozen=True)
class SpringConfig:
    """Numerics configuration threaded through every model layer."""

    mode: SpringMode = "dense"
    fmt: FixedPointFormat = SPRING_FORMAT
    # Deterministic rounding for activations on the fwd of *inference*;
    # training always uses SR (the paper's convergence argument).
    stochastic: bool = True
    # Kernel-dispatch policy: per-op backend pins + global default, under
    # which every kernel call site resolves its lowering.
    kernels: KernelPolicy = KernelPolicy()
    # Sparsity-aware backward pass (quant_sparse mode only): "auto" sends
    # dL/dX and dL/dW through the masked_matmul_dx/dw ops so tile
    # skipping applies to training, not just the forward pass (DESIGN.md
    # §8); "none" keeps the plain float32 product and autodiff.
    backward_sparsity: str = "auto"
    # Compute dtype of the dense baseline path.
    dense_dtype: jnp.dtype = jnp.bfloat16
    # §Perf levers for the quantized path:
    #  - weights updated by the SR fixed-point optimizer are ALREADY on the
    #    Q-grid: skip their runtime re-quantization (identity op)
    #  - operands can round-to-nearest (no RNG hash); SR stays on the MAC
    #    output, which is where the paper's convergence argument lives
    weights_pre_quantized: bool = False
    operand_rounding: str = "stochastic"  # "stochastic" | "nearest"

    def __post_init__(self):
        if self.backward_sparsity not in BACKWARD_SPARSITY_CHOICES:
            raise ValueError(
                f"unknown backward_sparsity {self.backward_sparsity!r}; "
                f"choose from {BACKWARD_SPARSITY_CHOICES}")

    @property
    def is_quantized(self) -> bool:
        return self.mode != "dense"

    @property
    def is_sparse(self) -> bool:
        return self.mode == "quant_sparse"

    @property
    def sparse_backward(self) -> bool:
        """True when the sparsity-aware custom_vjp backward is in force."""
        return self.is_sparse and self.backward_sparsity != "none"


DENSE = SpringConfig(mode="dense")
QUANT = SpringConfig(mode="quant")
QUANT_SPARSE = SpringConfig(mode="quant_sparse")

#: Canonical name -> base config for the three modes.  The single copy —
#: the launchers and the RunSpec resolver all import this one (the
#: per-launcher ``MODES = {...}`` dicts predate the RunSpec API).
MODES = {"dense": DENSE, "quant": QUANT, "quant_sparse": QUANT_SPARSE}


class KeyGen:
    """Deterministic per-trace key stream for SR sites.

    Each ``next()`` folds an incrementing counter into the base key, so a
    model with N rounding sites consumes N distinct, reproducible streams
    per step without threading keys through every layer signature.
    """

    def __init__(self, key: Optional[jax.Array]):
        self._key = key
        self._counter = 0

    def next(self) -> jax.Array:
        assert self._key is not None, "quantized mode requires an rng key"
        k = jax.random.fold_in(self._key, self._counter)
        self._counter += 1
        return k


def _q(x: jax.Array, cfg: SpringConfig, keys: Optional[KeyGen],
       role: str = "out") -> jax.Array:
    """Quantize one tensor onto the grid (STE for gradients).

    role: "act" | "weight" | "out" — weight quantization is skipped when
    weights_pre_quantized; operands may round-to-nearest (no RNG).
    """
    if role == "weight" and cfg.weights_pre_quantized:
        return x
    stochastic = cfg.stochastic
    if role in ("act", "weight") and cfg.operand_rounding == "nearest":
        stochastic = False
    with jax.named_scope("spring_quantize"):
        if stochastic and keys is not None:
            return ste_quantize_stochastic(keys.next(), x, cfg.fmt)
        return ste_quantize_nearest(x, cfg.fmt)


def spring_matmul(
    x: jax.Array,
    w: jax.Array,
    cfg: SpringConfig = DENSE,
    keys: Optional[KeyGen] = None,
    w_mask: Optional[jax.Array] = None,
    probe: Optional[jax.Array] = None,
) -> jax.Array:
    """``x @ w`` under the configured SPRING numerics.

    x: (..., K); w: (K, N); w_mask: optional (K, N) {0,1} pruning mask
    (the weight-sparsity source for LM archs; CNN activation sparsity
    arises naturally from ReLU and is captured by the value pattern);
    probe: the step's masked_matmul tile counter
    (``SpringContext.tile_probe``), used where the sparsity-aware
    backward is in force.
    """
    if cfg.mode == "dense":
        if w_mask is not None:
            w = w * w_mask.astype(w.dtype)
        return jnp.matmul(
            x.astype(cfg.dense_dtype), w.astype(cfg.dense_dtype)
        ).astype(cfg.dense_dtype)

    xq = _q(x, cfg, keys, role="act")
    if w_mask is not None:
        w = w * w_mask.astype(w.dtype)
    wq = _q(w, cfg, keys, role="weight")

    if cfg.sparse_backward and xq.ndim == 2 and wq.ndim == 2:
        # masked_matmul's custom_vjp: tile skipping forward and backward,
        # the SR rule of its lowering kept there; batched matmuls (rare:
        # MoE dispatch paths) keep the plain product, as the tiled kernels
        # are 2-D by construction
        y = mm_ops.quant_sparse_matmul(xq, wq, cfg.kernels, probe)
    else:
        # fp32 accumulate on the fixed-point grid (DESIGN.md deviation 2).
        y = jnp.matmul(xq.astype(jnp.float32), wq.astype(jnp.float32))

    # MAC-lane epilogue: stochastic rounding back to the storage format.
    return _q(y, cfg, keys)


# ---------------------------------------------------------------------------
# Sparsity-aware conv backward: both backward GEMMs of an NHWC conv are
# matmuls over patch matrices, so they route through the masked_matmul_dx/dw
# lowerings exactly like the fc layers (DESIGN.md §8):
#
#   dW = patches(x).T @ g      — the stashed ReLU-sparse activation re-read
#   dX = patches~(g) @ rot(w)  — the ReLU-masked cotangent, stride-dilated
#
# where patches~ extracts windows of the cotangent with lhs_dilation=stride
# and transpose-conv padding, and rot(w) is the spatially-flipped weight.
# ---------------------------------------------------------------------------

import functools as _functools

from jax import lax as _lax

_CONV_DNUMS = ("NHWC", "HWIO", "NHWC")


def _conv_nhwc(x, w, stride, padding):
    return _lax.conv_general_dilated(
        x, w, window_strides=stride, padding=padding,
        dimension_numbers=_CONV_DNUMS)


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_with_sparse_bwd(x, w, probe, stride, padding, lowerings):
    del probe
    return _conv_nhwc(x, w, stride, padding)


def _conv_sb_fwd(x, w, probe, stride, padding, lowerings):
    del probe
    return _conv_nhwc(x, w, stride, padding), (x, w)


def _conv_sb_bwd(stride, padding, lowerings, res, g):
    dx_lowering, dw_lowering = lowerings
    x, w = res
    n, h, wd, cin = x.shape
    r, s, _, cout = w.shape
    oh, ow = g.shape[1], g.shape[2]
    g2 = g.reshape(-1, cout)

    # dW: im2col patches of the stashed sparse activation x the cotangent.
    # conv_general_dilated_patches orders the patch features (Cin, R, S).
    p = _lax.conv_general_dilated_patches(
        x, filter_shape=(r, s), window_strides=stride, padding=padding,
        dimension_numbers=_CONV_DNUMS).reshape(-1, cin * r * s)
    dw, dw_counts = mm_ops.grad_product(dw_lowering, p, g2)
    dw = dw.reshape(cin, r, s, cout).transpose(1, 2, 0, 3)

    # dX: transpose-conv as dilated cotangent patches x flipped weights.
    fwd_pads = _lax.padtype_to_pads((h, wd), (r, s), stride, padding)
    bwd_pads = [
        (k - 1 - plo, dim - (odim - 1) * st + plo - 1)
        for (plo, _), k, dim, odim, st in zip(
            fwd_pads, (r, s), (h, wd), (oh, ow), stride)
    ]
    pg = _lax.conv_general_dilated_patches(
        g, filter_shape=(r, s), window_strides=(1, 1), padding=bwd_pads,
        lhs_dilation=stride, dimension_numbers=_CONV_DNUMS)
    pg = pg.reshape(-1, cout * r * s)
    wt = w[::-1, ::-1].transpose(3, 0, 1, 2).reshape(cout * r * s, cin)
    dx, dx_counts = mm_ops.grad_product(dx_lowering, pg, wt.T)
    # the tile counter (see spring_matmul): the forward is a plain conv,
    # so only the dx and dw calls count
    counts = mm_ops.probe_counts(jnp.zeros_like(dx_counts), dx_counts, dw_counts)
    return dx.reshape(n, h, wd, cin), dw, counts


_conv_with_sparse_bwd.defvjp(_conv_sb_fwd, _conv_sb_bwd)


def spring_conv2d(
    x: jax.Array,
    w: jax.Array,
    cfg: SpringConfig = DENSE,
    keys: Optional[KeyGen] = None,
    stride: tuple[int, int] = (1, 1),
    padding: str = "SAME",
    feature_group_count: int = 1,
    probe: Optional[jax.Array] = None,
) -> jax.Array:
    """NHWC conv under SPRING numerics. w: (R, S, Cin/g, Cout).  ``probe``
    counts the backward's masked_matmul tiles, as in ``spring_matmul``."""
    if cfg.mode == "dense":
        return jax.lax.conv_general_dilated(
            x.astype(cfg.dense_dtype),
            w.astype(cfg.dense_dtype),
            window_strides=stride,
            padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=feature_group_count,
        ).astype(cfg.dense_dtype)

    xq = _q(x, cfg, keys, role="act")
    wq = _q(w, cfg, keys, role="weight")
    if cfg.sparse_backward and feature_group_count == 1:
        # forward identical to the dense lowering below; backward GEMMs
        # (dX/dW) route through masked_matmul_dx/dw.  Grouped/depthwise
        # convs keep dense autodiff — their patch matrices interleave
        # groups and defeat the tiled kernels.
        if probe is None:
            probe = jnp.zeros((mm_ops.PROBE_SIZE,), jnp.float32)
        y = _conv_with_sparse_bwd(
            xq.astype(jnp.float32), wq.astype(jnp.float32), probe,
            tuple(stride), padding, mm_ops.backward_lowerings(cfg.kernels))
        return _q(y, cfg, keys)
    y = jax.lax.conv_general_dilated(
        xq.astype(jnp.float32),
        wq.astype(jnp.float32),
        window_strides=stride,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=feature_group_count,
    )
    return _q(y, cfg, keys)


def spring_einsum(
    spec: str,
    a: jax.Array,
    b: jax.Array,
    cfg: SpringConfig = DENSE,
    keys: Optional[KeyGen] = None,
) -> jax.Array:
    """Einsum under SPRING numerics (attention logits/combines, routing)."""
    if cfg.mode == "dense":
        return jnp.einsum(spec, a.astype(cfg.dense_dtype), b.astype(cfg.dense_dtype))
    aq = _q(a, cfg, keys, role="act")
    bq = _q(b, cfg, keys, role="act")
    y = jnp.einsum(spec, aq.astype(jnp.float32), bq.astype(jnp.float32))
    return _q(y, cfg, keys)
