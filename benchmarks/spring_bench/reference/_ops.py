"""Plain jax.numpy pieces shared by the float32 references.

Nothing here imports the program.  ``matmul`` is where a reference
states its precision: ``f32`` multiplies at ``Precision.HIGHEST`` (a
float32 product on a TPU otherwise runs in bfloat16 passes); ``bf16``
and ``int8`` are the lower precisions the correctness controls use
(``int8`` rounds each operand onto a symmetric per-tensor int8 grid, its
gradient passing straight through).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _fake_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(a, b, mode: str = "f32"):
    """``a @ b`` (batched over leading axes of ``a``) in float32 out."""
    if mode == "f32":
        return jnp.matmul(a, b, precision=HIGHEST)
    if mode == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if mode == "int8":
        return jnp.matmul(_fake_int8(a), _fake_int8(b), precision=HIGHEST)
    raise ValueError(f"unknown matmul mode {mode!r}")


def einsum(spec: str, a, b, mode: str = "f32"):
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if mode == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if mode == "int8":
        return jnp.einsum(spec, _fake_int8(a), _fake_int8(b), precision=HIGHEST)
    raise ValueError(f"unknown matmul mode {mode!r}")


def rmsnorm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def leaf_norms(tree) -> dict:
    """{"a/b/c": float32 norm} of every leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {path_name(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat}


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
