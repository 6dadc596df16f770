"""Property-based tests (hypothesis) for the binary-mask machinery:
``core/masking.py`` collapse/expand and the ``mask_compress`` pack/unpack
ops — random shapes and densities, bit-exact roundtrips, and packed wire
bytes matching the perfmodel traffic formula ``bits/elem = 20*density + 1``
(ISSUE 3, satellite 2)."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.masking import (
    MASK_WORD_BITS,
    collapse_to_front,
    expand_from_mask,
    mask_decode,
    mask_encode,
    pack_mask_bits,
    unpack_mask_bits,
)
from repro.kernels.mask_compress.ops import mask_pack, mask_unpack
from repro.memstash.format import (
    compress,
    decompress,
    formula_bits_per_elem,
    wire_bits,
)


# A fixed palette of lengths (aligned, unaligned, word-edge, large):
# hypothesis draws freely among them while keeping the jit-compilation
# count bounded on the 1-core CI container.
LENGTHS = [1, 3, 31, 32, 33, 64, 100, 257, 512, 1000, 1024, 1337, 2000]
WORD_COUNTS = [1, 2, 3, 7, 16, 31, 64]


def _vec(seed: int, n: int, density: float) -> jax.Array:
    key = jax.random.PRNGKey(seed)
    v = jax.random.normal(key, (n,))
    keep = jax.random.uniform(jax.random.fold_in(key, 1), (n,)) < density
    return v * keep


@given(st.integers(0, 2**31 - 1), st.sampled_from(LENGTHS),
       st.floats(0.0, 1.0))
@settings(deadline=None)
def test_collapse_expand_roundtrip_bit_exact(seed, n, density):
    """collapse_to_front/expand_from_mask at full capacity is the identity
    for any length and density (Fig. 7(c) shifter, both directions)."""
    x = _vec(seed, n, density)
    bits = x != 0.0
    collapsed = collapse_to_front(x, bits, n)
    restored = expand_from_mask(collapsed, bits)
    np.testing.assert_array_equal(np.asarray(restored), np.asarray(x))
    # live values sit contiguously at the front, tail is zero
    nnz = int(bits.sum())
    assert not np.any(np.asarray(collapsed[nnz:]))


@given(st.integers(0, 2**31 - 1), st.sampled_from(LENGTHS),
       st.floats(0.0, 1.0))
@settings(deadline=None)
def test_mask_encode_decode_roundtrip(seed, n, density):
    x = _vec(seed, n, density)
    mv = mask_encode(x)
    np.testing.assert_array_equal(np.asarray(mask_decode(mv)), np.asarray(x))
    assert int(mv.nnz) == int(np.count_nonzero(np.asarray(x)))


@given(st.integers(0, 2**31 - 1), st.sampled_from(LENGTHS))
@settings(deadline=None)
def test_pack_unpack_mask_bits_roundtrip(seed, n):
    """pack_mask_bits/unpack_mask_bits roundtrip bit-exactly for any
    length, aligned or not."""
    rng = np.random.default_rng(seed)
    bits = jnp.asarray(rng.integers(0, 2, n, dtype=np.uint32).astype(bool))
    words = pack_mask_bits(bits)
    assert words.shape[0] == -(-n // MASK_WORD_BITS)
    np.testing.assert_array_equal(
        np.asarray(unpack_mask_bits(words, n)), np.asarray(bits))


@given(st.integers(0, 2**31 - 1), st.sampled_from(LENGTHS),
       st.floats(0.0, 1.0))
@settings(deadline=None)
def test_mask_compress_op_pack_unpack_roundtrip(seed, n, density):
    """The registry-dispatched mask_pack/mask_unpack ops roundtrip the
    occupancy pattern of any-shaped input (the packed words cover the
    kernel's lane padding; the first ceil(n/32) words carry the data)."""
    x = _vec(seed, n, density)
    words = mask_pack(x)
    got = mask_unpack(words, n)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(x) != 0.0)


@given(st.integers(0, 2**31 - 1), st.sampled_from(WORD_COUNTS),
       st.floats(0.0, 1.0))
@settings(deadline=None)
def test_packed_wire_bits_match_perfmodel_formula(seed, words, density):
    """For word-aligned lengths the measured stash wire bits are EXACTLY
    the perfmodel formula ``n * (20*density + 1)`` at the measured
    density — the single-sourced traffic accounting (paper Fig. 5)."""
    n = words * MASK_WORD_BITS
    x = _vec(seed, n, density)
    sv = compress(x)
    measured_density = int(sv.nnz) / n
    want_bits = n * formula_bits_per_elem(measured_density, 20)
    np.testing.assert_allclose(float(wire_bits(sv, 20)), want_bits, rtol=1e-12)
    np.testing.assert_array_equal(np.asarray(decompress(sv)), np.asarray(x))


@given(st.integers(0, 2**31 - 1), st.sampled_from(LENGTHS),
       st.floats(0.0, 1.0))
@settings(deadline=None)
def test_wire_bits_unaligned_within_one_word_of_formula(seed, n, density):
    """Unaligned lengths pay only the final word's padding: measured wire
    bits exceed the formula by the mask tail, strictly < 32 bits."""
    x = _vec(seed, n, density)
    sv = compress(x)
    formula = int(sv.nnz) * 20 + n  # value bits + 1 mask bit/elem
    pad = float(wire_bits(sv, 20)) - formula
    assert 0 <= pad < MASK_WORD_BITS
