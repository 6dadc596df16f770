"""Public wrappers for mask packing / dangling filtering with padding.

Three registered ops: ``mask_pack`` (values -> packed occupancy words),
``mask_unpack`` (its inverse) and ``dangling_filter`` (zero each operand
where the other is zero — SPRING's pre-compute filter).  ``mask_unpack``
is a shift-and-test on the VPU lanes on every backend and registers only
its ``ref`` lowering.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.mask_compress.mc_kernel import COLS, ROWS, dangling_filter_pallas, mask_pack_pallas


def _pad2d(x: jax.Array) -> tuple[jax.Array, int, int]:
    flat = x.reshape(-1)
    n = flat.shape[0]
    block = ROWS * COLS
    padded = (n + block - 1) // block * block
    return jnp.pad(flat, (0, padded - n)).reshape(-1, COLS), n, padded


@jax.jit
def _pack_ref(x):
    from repro.core.masking import pack_mask_bits

    x2d, _, _ = _pad2d(x)
    return pack_mask_bits(x2d.reshape(-1) != 0.0)


@partial(jax.jit, static_argnames=("interpret",))
def _pack_kernel(x, *, interpret):
    x2d, _, _ = _pad2d(x)
    words = mask_pack_pallas(x2d, interpret=interpret)
    return words.reshape(-1)


@partial(jax.jit, static_argnames=("length",))
def _unpack_ref(words, length):
    from repro.core.masking import unpack_mask_bits

    return unpack_mask_bits(words.reshape(-1), length)


@jax.jit
def _dangling_ref(a, w):
    joint = (a != 0.0) & (w != 0.0)
    return jnp.where(joint, a, 0.0), jnp.where(joint, w, 0.0)


@partial(jax.jit, static_argnames=("interpret",))
def _dangling_kernel(a, w, *, interpret):
    a2d, n, _ = _pad2d(a)
    w2d, _, _ = _pad2d(w)
    af, wf = dangling_filter_pallas(a2d, w2d, interpret=interpret)
    return af.reshape(-1)[:n].reshape(a.shape), wf.reshape(-1)[:n].reshape(w.shape)


def _sparse_vec(seed: int, n: int, sparsity: float) -> jax.Array:
    key = jax.random.PRNGKey(seed)
    return jax.random.normal(key, (n,)) * (
        jax.random.uniform(jax.random.fold_in(key, 1), (n,)) > sparsity)


def _pack_examples() -> list:
    return [((_sparse_vec(5, 777, 0.4),), {}),
            ((_sparse_vec(6, 4096, 0.6),), {}),
            ((_sparse_vec(7, 1000, 0.5).reshape(10, 100),), {})]


def _dangling_examples() -> list:
    return [((_sparse_vec(0, 5000, 0.5), _sparse_vec(2, 5000, 0.6)), {}),
            ((_sparse_vec(3, 640, 0.3).reshape(32, 20),
              _sparse_vec(4, 640, 0.7).reshape(32, 20)), {})]


registry.register_op("mask_pack", oracle="ref", examples=_pack_examples,
                     compare={"kind": "exact"})
registry.register_impl("mask_pack", "ref", priority=10)(_pack_ref)
registry.register_impl("mask_pack", "interpret", selectable=False)(
    partial(_pack_kernel, interpret=True))
registry.register_impl("mask_pack", "pallas", priority=30,
                       available=registry.on_tpu)(
    partial(_pack_kernel, interpret=False))

registry.register_op("mask_unpack", oracle="ref")
registry.register_impl("mask_unpack", "ref", priority=10)(_unpack_ref)

registry.register_op("dangling_filter", oracle="ref",
                     examples=_dangling_examples, compare={"kind": "exact"})
registry.register_impl("dangling_filter", "ref", priority=10)(_dangling_ref)
registry.register_impl("dangling_filter", "interpret", selectable=False)(
    partial(_dangling_kernel, interpret=True))
registry.register_impl("dangling_filter", "pallas", priority=30,
                       available=registry.on_tpu)(
    partial(_dangling_kernel, interpret=False))


def mask_pack(x: jax.Array, impl: str | None = None) -> jax.Array:
    """Flattened packed occupancy mask words for any-shaped ``x``."""
    kimpl = registry.resolve("mask_pack", impl)
    words = kimpl.fn(x)
    if registry.metrics_active() and not isinstance(words, jax.core.Tracer):
        # measured wire bytes of the packed representation: 1 bit/elem in
        # whole uint32 words, ceil(n/32)*4 — the mask term of the
        # perfmodel traffic formula, matching memstash accounting (the
        # kernel's ROWS*COLS lane padding is not wire traffic)
        registry.note_metric("mask_pack", wire_bytes=float(-(-x.size // 32) * 4))
    return words


def mask_unpack(words: jax.Array, length: int, impl: str | None = None) -> jax.Array:
    """Packed mask words -> (length,) bool occupancy (``mask_pack`` inverse)."""
    kimpl = registry.resolve("mask_unpack", impl)
    return kimpl.fn(words, length)


def dangling_filter(a: jax.Array, w: jax.Array,
                    impl: str | None = None) -> tuple[jax.Array, jax.Array]:
    """Zero each operand where the other is zero (pre-compute filter)."""
    assert a.shape == w.shape
    kimpl = registry.resolve("dangling_filter", impl)
    return kimpl.fn(a, w)
