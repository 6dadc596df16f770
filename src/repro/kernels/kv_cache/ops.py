"""KV-cache block compression ops: ``kv_pack`` / ``kv_unpack``.

The inference-side twin of the memstash activation format (DESIGN.md
§4.3): one flattened KV block is stored as its non-zeros collapsed to the
front of a dense-length value buffer (bit-exact round trip, values kept
verbatim in the block's own dtype) plus a 1-bit-per-element packed
occupancy mask.  The serving engine's slot pool stores every seq-bearing
cache leaf in this form and unpacks it on read inside the decode step
(``repro.serving.kvpool``); the wire accounting is the paper's
``bits/elem = 20*density + 1`` interface formula, single-sourced with
``memstash.format.formula_bits_per_elem``.

Implementation ladder:

  ref        cumsum-scatter collapse + reshape-based mask pack (the
             vectorized oracle, shared with core/masking.py);
  jnp        stable-argsort collapse + gather-based word pack — a second,
             independently-derived exact lowering (cross-checked in CI);
  interpret  mask words from the Pallas ``mask_pack`` kernel in interpret
             mode (lane-padded, trimmed to the canonical word count);
  pallas     the same kernel compiled on TPU.

``kv_unpack`` is a shift-and-test + gather on every backend and registers
only its ``ref`` and ``jnp`` lowerings.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.masking import (
    MASK_WORD_BITS,
    collapse_to_front,
    pack_mask_bits,
)
from repro.kernels import registry

#: SPRING storage width of one cached value on the RRAM interface
#: (IL4 + FL16 fixed point — SpringDesign.value_bits).
KV_VALUE_BITS = 20


def _n_words(n: int) -> int:
    return (n + MASK_WORD_BITS - 1) // MASK_WORD_BITS


@jax.jit
def _pack_ref(x):
    flat = x.reshape(-1)
    n = flat.shape[0]
    bits = flat != 0
    return {
        "values": collapse_to_front(flat, bits, n),
        "mask": pack_mask_bits(bits),
        "nnz": bits.sum().astype(jnp.int32),
    }


@jax.jit
def _pack_jnp(x):
    # independent exact lowering: live elements first via a stable argsort
    # on the occupancy bits, dead/overflow tail zeroed behind nnz
    flat = x.reshape(-1)
    n = flat.shape[0]
    bits = flat != 0
    order = jnp.argsort(jnp.logical_not(bits), stable=True)
    nnz = bits.sum().astype(jnp.int32)
    gathered = flat[order]
    values = jnp.where(jnp.arange(n) < nnz, gathered,
                       jnp.zeros((), flat.dtype))
    # gather-based word pack (vs the ref's reshape-based pack)
    word = jnp.arange(n) // MASK_WORD_BITS
    shift = (jnp.arange(n) % MASK_WORD_BITS).astype(jnp.uint32)
    contrib = jnp.where(bits, jnp.uint32(1) << shift, jnp.uint32(0))
    words = jnp.zeros((_n_words(n),), jnp.uint32).at[word].add(contrib)
    return {"values": values, "mask": words, "nnz": nnz}


@partial(jax.jit, static_argnames=("interpret",))
def _pack_kernel(x, *, interpret):
    from repro.kernels.mask_compress.ops import _pad2d
    from repro.kernels.mask_compress.mc_kernel import mask_pack_pallas

    flat = x.reshape(-1)
    n = flat.shape[0]
    bits = flat != 0
    x2d, _, _ = _pad2d(flat)
    # lane-padded kernel words are bit-compatible with the canonical
    # layout (word j covers elements 32j..32j+31); the pad tail is zero
    words = mask_pack_pallas(x2d, interpret=interpret).reshape(-1)[:_n_words(n)]
    return {
        "values": collapse_to_front(flat, bits, n),
        "mask": words,
        "nnz": bits.sum().astype(jnp.int32),
    }


@partial(jax.jit, static_argnames=("length",))
def _unpack_ref(values, mask, *, length):
    from repro.core.masking import expand_from_mask, unpack_mask_bits

    bits = unpack_mask_bits(mask, length)
    return expand_from_mask(values, bits)


@partial(jax.jit, static_argnames=("length",))
def _unpack_jnp(values, mask, *, length):
    # gather-based shift-and-test (vs the ref's reshape-based unpack)
    idx = jnp.arange(length)
    shift = (idx % MASK_WORD_BITS).astype(jnp.uint32)
    bits = (mask[idx // MASK_WORD_BITS] >> shift) & jnp.uint32(1)
    src = jnp.cumsum(bits.astype(jnp.int32)) - 1
    cap = values.shape[0]
    live = (bits == 1) & (src < cap)
    gathered = values[jnp.clip(src, 0, cap - 1)]
    return jnp.where(live, gathered, jnp.zeros((), values.dtype))


# -- registry examples --------------------------------------------------------


def _kv_block(seed: int, n: int, live_rows: int, total_rows: int,
              dtype=jnp.bfloat16) -> jax.Array:
    """A slot-pool-shaped block: the first ``live_rows`` of ``total_rows``
    carry dense KV values, the unfilled tail is zero (the natural sparsity
    pattern of a partially-decoded slot)."""
    key = jax.random.PRNGKey(seed)
    per_row = n // total_rows
    x = jax.random.normal(key, (total_rows, per_row), jnp.float32)
    live = jnp.arange(total_rows)[:, None] < live_rows
    return jnp.where(live, x, 0.0).astype(dtype).reshape(-1)[:n]


def _pack_examples() -> list:
    return [
        ((_kv_block(0, 4096, 9, 16),), {}),                  # bf16, word-aligned
        ((_kv_block(1, 4096, 16, 16, jnp.float32),), {}),    # fully dense
        ((_kv_block(2, 1000, 3, 10, jnp.float32),), {}),     # unaligned length
        ((jnp.zeros((640,), jnp.bfloat16),), {}),            # empty slot
    ]


def _unpack_examples() -> list:
    out = []
    for (x,), _ in _pack_examples():
        packed = _pack_ref(x)
        out.append(((packed["values"], packed["mask"]),
                    {"length": int(x.size)}))
    return out


registry.register_op("kv_pack", oracle="ref", examples=_pack_examples,
                     compare={"kind": "exact"})
registry.register_impl("kv_pack", "ref", priority=10)(_pack_ref)
registry.register_impl("kv_pack", "jnp", priority=20)(_pack_jnp)
registry.register_impl("kv_pack", "interpret", selectable=False)(
    partial(_pack_kernel, interpret=True))
registry.register_impl("kv_pack", "pallas", priority=30,
                       available=registry.on_tpu)(
    partial(_pack_kernel, interpret=False))

registry.register_op("kv_unpack", oracle="ref", examples=_unpack_examples,
                     compare={"kind": "exact"})
registry.register_impl("kv_unpack", "ref", priority=10)(_unpack_ref)
registry.register_impl("kv_unpack", "jnp", priority=20)(_unpack_jnp)


# -- public wrappers ----------------------------------------------------------


def kv_wire_bits(nnz, length: int, value_bits: int = KV_VALUE_BITS):
    """Bits the memory interface moves for one packed block: live values
    at the SPRING 20-bit width + the packed mask words actually stored.
    At word alignment this is exactly ``length * (value_bits*density + 1)``
    — the ``formula_bits_per_elem`` accounting (cross-checked in tests)."""
    return nnz * value_bits + _n_words(length) * MASK_WORD_BITS


def kv_pack(x: jax.Array, impl: str | None = None) -> dict:
    """Flattened KV block -> {"values", "mask", "nnz"} (bit-exact format).

    ``values`` keeps ``x``'s dtype and dense length; the only
    canonicalization is ``-0.0 -> +0.0`` (its occupancy bit is 0), which
    is invisible to the attention math.
    """
    kimpl = registry.resolve("kv_pack", impl)
    packed = kimpl.fn(x)
    if registry.metrics_active() and not isinstance(
            packed["nnz"], jax.core.Tracer):
        nnz = float(packed["nnz"])
        registry.note_metric(
            "kv_pack",
            wire_bytes=float(kv_wire_bits(nnz, x.size)) / 8.0,
            density=nnz / float(x.size),
        )
    return packed


def kv_unpack(values: jax.Array, mask: jax.Array, length: int,
              impl: str | None = None) -> jax.Array:
    """Packed block -> flat dense ``(length,)`` (``kv_pack`` inverse)."""
    kimpl = registry.resolve("kv_unpack", impl)
    return kimpl.fn(values, mask, length=length)


def kv_probe(density: float = 0.5, size: int = 1 << 14,
             impl: str | None = None) -> dict:
    """Eager KV-compression probe for dry-run attribution.

    A lowered decode cell never executes, so this packs one synthetic KV
    block at the given element density and reports what the registry-
    resolved ``kv_pack`` measured: wire bytes, the reduction vs a dense
    fp32 block, and the measured-over-formula ratio (1.0 at word
    alignment — the ``20*density + 1`` cross-check).
    """
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (size,))
    keep = jax.random.uniform(jax.random.fold_in(key, 1), (size,)) < density
    x = jnp.where(keep, x, 0.0)
    packed = kv_pack(x, impl=impl)
    nnz = int(packed["nnz"])
    wire = float(kv_wire_bits(nnz, size)) / 8.0
    from repro.memstash.format import formula_bits_per_elem

    formula = size * formula_bits_per_elem(nnz / size, KV_VALUE_BITS) / 8.0
    return {
        "density": nnz / size,
        "wire_bytes": wire,
        "compression_vs_fp32": size * 4.0 / wire,
        "wire_vs_formula": wire / formula,
        "impl": registry.resolve("kv_pack", impl, _count=False).name,
    }
