"""Foundational layers. Functional style: ``*_init(key,...) -> params`` /
``*_apply(params, x, ctx, ...)``.  Every matmul funnels through
``core.spring_ops`` so the paper's numerics (dense | quant | quant_sparse)
apply uniformly across all architectures (DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.spring_ops import DENSE, KeyGen, SpringConfig, spring_matmul
from repro.memstash.config import MemstashConfig
from repro.runtime.sharding import constrain


@dataclasses.dataclass
class SpringContext:
    """Per-call numerics context threaded through every layer."""

    cfg: SpringConfig = DENSE
    keys: Optional[KeyGen] = None
    # Magnitude-pruning ratio for weight sparsity (LM archs; paper §2.2
    # cites 20-80% weight sparsity).  Masks are derived inline from a
    # Gaussian-calibrated threshold — no stored mask tensors.
    prune_ratio: float = 0.0
    # int8 KV cache (SPRING reduced precision applied to serving state)
    int8_cache: bool = False
    # Compressed-activation-stash policy for training (memstash subsystem);
    # None means every stash point resolves to "none".
    memstash: Optional[MemstashConfig] = None
    # masked_matmul tile counter of a training step: a zero float32 vector
    # the loss is differentiated against; its gradient sums every call's
    # issued/total grid steps (kernels/masked_matmul/ops.py).  Code
    # that hands the context to a custom_vjp passes it as an input.
    tile_probe: Optional[jax.Array] = None

    def stash_policy(self, name: str, elems: Optional[int] = None) -> str:
        """Resolve the checkpoint policy for one named stash point."""
        if self.memstash is None:
            return "none"
        return self.memstash.policy_for(name, elems)

    def kernel_impl(self, op: str, **caps) -> str:
        """Resolve a kernel op under this context's KernelPolicy.

        Returns the concrete impl name model code passes as ``impl=`` so
        every kernel call site dispatches through the registry with the
        config-threaded policy (CLI ``--kernel-impl``) taking effect.
        """
        from repro.kernels import registry

        return registry.resolve_with(self.cfg.kernels, op, **caps).name

    def kernel_pinned(self, op: str) -> Optional[str]:
        """Non-auto impl explicitly pinned for ``op``, else None.

        Used by call sites that have their own preferred non-kernel
        lowering (e.g. chunked jnp attention) and only reroute through
        the kernel wrapper when the user pinned a backend.
        """
        from repro.kernels import registry

        pol = self.cfg.kernels
        if pol.is_auto:
            pol = registry.current_policy()
        name = pol.impl_for(op)
        if name == "auto":
            return None
        if op in dict(pol.overrides):
            return name  # per-op pin: strict
        # soft global default: applies only where the op registers it
        return name if name in registry.impls(op) else None

    def maybe_prune(self, w: jax.Array) -> jax.Array:
        if self.prune_ratio <= 0.0:
            return w
        # For w ~ N(0, s): P(|w| < t) = erf(t / (s*sqrt(2)))
        t = jax.scipy.special.erfinv(jnp.float32(self.prune_ratio)) * math.sqrt(2.0)
        std = jnp.std(w.astype(jnp.float32)) + 1e-12
        return jnp.where(jnp.abs(w) >= t * std, w, 0.0).astype(w.dtype)


def dense_init(key, d_in: int, d_out: int, *, bias: bool = False, scale: float | None = None):
    if scale is None:
        scale = 1.0 / math.sqrt(d_in)
    p = {"kernel": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale}
    if bias:
        p["bias"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense_apply(
    params,
    x: jax.Array,
    ctx: SpringContext,
    *,
    w_logical: tuple = (None, None),
    out_logical: Optional[tuple] = None,
) -> jax.Array:
    w = constrain(params["kernel"], w_logical)
    w = ctx.maybe_prune(w)
    shape = x.shape
    y = spring_matmul(x.reshape(-1, shape[-1]), w, ctx.cfg, ctx.keys,
                      probe=ctx.tile_probe)
    y = y.reshape(*shape[:-1], w.shape[-1])
    if "bias" in params:
        y = (y + params["bias"].astype(y.dtype)).astype(y.dtype)
    if out_logical is not None:
        y = constrain(y, out_logical)
    return y


def embed_init(key, vocab: int, d: int):
    return {"embedding": jax.random.normal(key, (vocab, d), jnp.float32) * 0.02}


def embed_apply(params, tokens: jax.Array, ctx: SpringContext) -> jax.Array:
    emb = constrain(params["embedding"], ("w_vocab", "w_embed"))
    # quantized modes carry fp32 activations (the Q4.16 grid does not fit
    # in bf16); dense mode uses the configured compute dtype.
    act_dtype = jnp.float32 if ctx.cfg.is_quantized else ctx.cfg.dense_dtype
    y = jnp.take(emb, tokens, axis=0).astype(act_dtype)
    return constrain(y, ("batch", "seq", "embed"))


def rmsnorm_init(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm_apply(params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(x.dtype)


def layernorm_init(d: int):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm_apply(params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class YarnSpec:
    """YaRN rope scaling (arXiv:2309.00071), in the fields of DeepSeek-V2's
    ``rope_scaling``: frequencies blended between extrapolated (the
    original) and interpolated (divided by ``factor``) along a linear
    ramp between the correction dims of ``beta_fast`` and ``beta_slow``
    rotations over ``original_max_position``; cos and sin scaled by
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_ramp(d: int, theta: float, yarn: YarnSpec) -> list:
    """Per frequency (d/2 of them) the share taken from the interpolated
    frequency: 0 below the correction dim of ``beta_fast``, 1 above that
    of ``beta_slow``, linear between."""
    def dim(rotations):
        return d * math.log(yarn.original_max_position / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim(yarn.beta_fast)), 0)
    high = min(math.ceil(dim(yarn.beta_slow)), d - 1)
    high = high + 0.001 if high == low else high
    return [min(max((i - low) / (high - low), 0.0), 1.0) for i in range(d // 2)]


def rope_apply(x: jax.Array, positions: jax.Array, theta: float = 10000.0, *,
               yarn: Optional[YarnSpec] = None, interleaved: bool = False) -> jax.Array:
    """x: (B, S, H, D) with D even; positions: (B, S) int32.

    Rotates the halves of D (NeoX layout).  ``interleaved`` takes the
    pairs (2i, 2i+1) instead, as DeepSeek-V2 does: it de-interleaves them
    into halves first, so the output is in the halves layout (q and k
    alike, which leaves their dot product unchanged)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    mscale = 1.0
    if yarn is not None:
        ramp = jnp.asarray(yarn_ramp(d, theta, yarn), jnp.float32)
        inv_freq = inv_freq / yarn.factor * ramp + inv_freq * (1.0 - ramp)
        mscale = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (B,S,D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    xf = x.astype(jnp.float32)
    if interleaved:
        xf = jnp.concatenate([xf[..., 0::2], xf[..., 1::2]], axis=-1)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Feed-forward blocks.
# --------------------------------------------------------------------------


def swiglu_init(key, d: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, d, d_ff),
        "up": dense_init(k2, d, d_ff),
        "down": dense_init(k3, d_ff, d),
    }


def swiglu_apply(params, x: jax.Array, ctx: SpringContext) -> jax.Array:
    g = dense_apply(params["gate"], x, ctx, w_logical=("w_embed", "w_mlp"))
    u = dense_apply(params["up"], x, ctx, w_logical=("w_embed", "w_mlp"))
    h = constrain(jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * u, ("batch", "seq", "mlp_act"))
    return dense_apply(params["down"], h, ctx, w_logical=("w_mlp", "w_embed"),
                       out_logical=("batch", "seq", "embed"))


def gelu_mlp_init(key, d: int, d_ff: int, *, bias: bool = True):
    k1, k2 = jax.random.split(key)
    return {"fc1": dense_init(k1, d, d_ff, bias=bias), "fc2": dense_init(k2, d_ff, d, bias=bias)}


def gelu_mlp_apply(params, x: jax.Array, ctx: SpringContext) -> jax.Array:
    h = dense_apply(params["fc1"], x, ctx, w_logical=("w_embed", "w_mlp"))
    h = constrain(jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype), ("batch", "seq", "mlp_act"))
    return dense_apply(params["fc2"], h, ctx, w_logical=("w_mlp", "w_embed"),
                       out_logical=("batch", "seq", "embed"))
