"""SGD-momentum and AdamW, built from scratch (no optax), with the
SPRING twist: optional fixed-point Q(IL,FL) master weights updated via
stochastic rounding (paper §3.2 — the mechanism that keeps reduced-
precision *training* convergent).  ``weight_format=None`` gives standard
fp32 training (the dense baseline).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.fixedpoint import FixedPointFormat, quantize_stochastic


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    kind: str = "adamw"  # "adamw" | "sgdm"
    lr: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    momentum: float = 0.9
    grad_clip: float = 1.0
    # SPRING reduced-precision master weights (None -> fp32 baseline)
    weight_format: Optional[FixedPointFormat] = None
    warmup_steps: int = 0


class OptState(NamedTuple):
    step: jax.Array
    m: Any  # first moment / momentum
    v: Any  # second moment (adamw) or None-like zeros (sgdm)


def _zeros_like_tree(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree_util.tree_leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / (gn + 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), gn


def _schedule(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    lr = jnp.float32(cfg.lr)
    if cfg.warmup_steps > 0:
        lr = lr * jnp.minimum(1.0, (step + 1) / cfg.warmup_steps)
    return lr


def _finalize_weights(new_p, cfg: OptimizerConfig, key: Optional[jax.Array]):
    """SR-quantize updated weights onto the Q(IL,FL) grid when configured."""
    if cfg.weight_format is None:
        return new_p
    assert key is not None, "fixed-point weight update needs an rng key"
    leaves, treedef = jax.tree_util.tree_flatten(new_p)
    with jax.named_scope("spring_quantize"):
        keys = jax.random.split(key, len(leaves))
        out = [quantize_stochastic(k, p, cfg.weight_format) for k, p in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


# -- AdamW -------------------------------------------------------------------


def adamw_init(params) -> OptState:
    return OptState(jnp.zeros((), jnp.int32), _zeros_like_tree(params), _zeros_like_tree(params))


def adamw_update(
    cfg: OptimizerConfig,
    grads,
    state: OptState,
    params,
    key: Optional[jax.Array] = None,
):
    grads, gn = clip_by_global_norm(grads, cfg.grad_clip)
    step = state.step + 1
    lr = _schedule(cfg, state.step)
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    new_m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32), state.m, grads)
    new_v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)), state.v, grads)

    def upd(p, m, v):
        update = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
        return (p.astype(jnp.float32) - lr * (update + cfg.weight_decay * p.astype(jnp.float32))).astype(p.dtype)

    new_p = jax.tree_util.tree_map(upd, params, new_m, new_v)
    new_p = _finalize_weights(new_p, cfg, key)
    return new_p, OptState(step, new_m, new_v), {"grad_norm": gn, "lr": lr}


# -- SGD momentum ------------------------------------------------------------


def sgdm_init(params) -> OptState:
    return OptState(jnp.zeros((), jnp.int32), _zeros_like_tree(params),
                    jax.tree_util.tree_map(lambda p: jnp.zeros((), jnp.float32), params))


def sgdm_update(
    cfg: OptimizerConfig,
    grads,
    state: OptState,
    params,
    key: Optional[jax.Array] = None,
):
    grads, gn = clip_by_global_norm(grads, cfg.grad_clip)
    step = state.step + 1
    lr = _schedule(cfg, state.step)
    new_m = jax.tree_util.tree_map(
        lambda m, g: cfg.momentum * m + g.astype(jnp.float32), state.m, grads
    )
    new_p = jax.tree_util.tree_map(
        lambda p, m: (p.astype(jnp.float32) - lr * (m + cfg.weight_decay * p.astype(jnp.float32))).astype(p.dtype),
        params, new_m,
    )
    new_p = _finalize_weights(new_p, cfg, key)
    return new_p, OptState(step, new_m, state.v), {"grad_norm": gn, "lr": lr}


def make_optimizer(cfg: OptimizerConfig):
    if cfg.kind == "adamw":
        return adamw_init, lambda g, s, p, key=None: adamw_update(cfg, g, s, p, key)
    if cfg.kind == "sgdm":
        return sgdm_init, lambda g, s, p, key=None: sgdm_update(cfg, g, s, p, key)
    raise ValueError(cfg.kind)
