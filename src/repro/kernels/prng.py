"""Counter-based PRNG shared by Pallas kernels and their jnp oracles.

SPRING drives its stochastic-rounding module from an LFSR (paper §3.2).
An LFSR is bit-serial; the TPU-native equivalent in the same
linear-shift-register family is a counter-based xorshift/finalizer hash:
each output element hashes (seed, element counter) into uniform bits, so
the stream is stateless, order-independent and identical between the
kernel and the pure-jnp reference (exact-equality testable).

The mix is the murmur3/splitmix 32-bit finalizer — full-avalanche, built
from xor-shift-multiply ops that exist on the TPU VPU and in interpret
mode alike.
"""

from __future__ import annotations

import jax.numpy as jnp


def hash_uint32(counter: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """Full-avalanche 32-bit finalizer of (counter ^ seed-mixed) values.

    counter: any-shape uint32 (element indices); seed: scalar uint32.
    Returns uniform uint32 of counter.shape.
    """
    z = counter.astype(jnp.uint32) + (seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
    z = (z ^ (z >> jnp.uint32(16))) * jnp.uint32(0x7FEB352D)
    z = (z ^ (z >> jnp.uint32(15))) * jnp.uint32(0x846CA68B)
    z = z ^ (z >> jnp.uint32(16))
    return z


def uniform_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 -> float32 uniform in [0, 1) with 24-bit resolution.

    The shifted value is below 2**24, so it converts through int32 exactly
    (Mosaic has no uint32 -> float32 cast).
    """
    top24 = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top24.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
