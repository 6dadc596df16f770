"""KV-cache compression roundtrips (ISSUE 4, satellite 3).

``kv_pack``/``kv_unpack`` are bit-exact against the element-serial numpy
oracle, their wire accounting matches the paper's ``20*density + 1``
bits/elem formula exactly at word alignment, and the serving slot pool
(kvpool) round-trips a real model cache bit-exactly — including install /
merge / release slot surgery.

The registry parity harness (tests/test_kernel_registry.py) additionally
cross-checks every registered (op, impl) pair on the registered examples;
completeness enforcement covers the kv_cache package like any other.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import registry
from repro.kernels.kv_cache.ops import (
    KV_VALUE_BITS,
    kv_pack,
    kv_unpack,
    kv_wire_bits,
)
from repro.kernels.kv_cache.ref import (
    kv_pack_reference,
    kv_unpack_reference,
    kv_wire_bits_reference,
)
from repro.memstash.format import formula_bits_per_elem

pytestmark = pytest.mark.serving


def _block(seed, n, density, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n,))
    keep = jax.random.uniform(jax.random.fold_in(key, 1), (n,)) < density
    return (x * keep).astype(dtype)


@pytest.mark.parametrize("n,density,dtype", [
    (1024, 0.0, jnp.float32),
    (1024, 0.5, jnp.float32),
    (4096, 0.37, jnp.bfloat16),
    (1000, 0.8, jnp.bfloat16),   # unaligned length
    (33, 1.0, jnp.float32),
])
def test_pack_matches_serial_oracle_and_roundtrips(n, density, dtype):
    x = _block(n, n, density, dtype)
    packed = kv_pack(x)
    vr, wr, nr = kv_pack_reference(np.asarray(x))
    np.testing.assert_array_equal(np.asarray(packed["values"]).view(np.uint16)
                                  if dtype == jnp.bfloat16 else np.asarray(packed["values"]),
                                  vr.view(np.uint16) if dtype == jnp.bfloat16 else vr)
    np.testing.assert_array_equal(np.asarray(packed["mask"]), wr)
    assert int(packed["nnz"]) == nr
    dec = kv_unpack(packed["values"], packed["mask"], n)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(x))
    ser = kv_unpack_reference(vr, wr, n)
    np.testing.assert_array_equal(np.asarray(dec), ser)


def test_every_cpu_impl_roundtrips_bit_exactly():
    x = _block(7, 2048, 0.45, jnp.bfloat16)
    want = np.asarray(x)
    for pack_impl in ("ref", "jnp", "interpret"):
        packed = kv_pack(x, impl=pack_impl)
        for unpack_impl in ("ref", "jnp"):
            dec = kv_unpack(packed["values"], packed["mask"], x.size,
                            impl=unpack_impl)
            np.testing.assert_array_equal(np.asarray(dec), want)


def test_negative_zero_canonicalizes_without_changing_math():
    x = jnp.asarray([1.0, -0.0, 0.0, -2.5], jnp.float32)
    packed = kv_pack(x)
    assert int(packed["nnz"]) == 2  # -0.0 is not occupancy
    dec = np.asarray(kv_unpack(packed["values"], packed["mask"], 4))
    np.testing.assert_array_equal(dec, [1.0, 0.0, 0.0, -2.5])


def test_wire_bits_match_formula_at_word_alignment():
    """kv_wire_bits == n * (20*density + 1) exactly when 32 | n — the
    single-sourced perfmodel/memstash traffic formula."""
    for n, density in [(32, 0.5), (1024, 0.25), (4096, 1.0), (2048, 0.0)]:
        x = _block(n, n, density)
        packed = kv_pack(x)
        nnz = int(packed["nnz"])
        measured = float(kv_wire_bits(nnz, n))
        formula = n * formula_bits_per_elem(nnz / n, KV_VALUE_BITS)
        assert measured == formula, (n, density, measured, formula)
        assert measured == kv_wire_bits_reference(nnz, n)
    # off alignment the measured mask words are whole uint32s (>= formula)
    x = _block(5, 1000, 0.5)
    packed = kv_pack(x)
    nnz = int(packed["nnz"])
    assert float(kv_wire_bits(nnz, 1000)) == nnz * KV_VALUE_BITS + 32 * 32


def test_perfmodel_helpers_consume_eager_kv_metrics():
    """measured_kv_density / measured_kv_wire_bytes ground spring_eval's
    decode-phase traffic term from eager kv_pack rows (kv_probe-style)."""
    from repro.kernels.kv_cache.ops import kv_probe
    from repro.perfmodel.spring_model import (
        measured_kv_density,
        measured_kv_wire_bytes,
    )

    with registry.record_kernel_metrics() as rows:
        probe = kv_probe(0.4, size=1 << 12)
        kv_probe(0.4, size=1 << 12)
    d = measured_kv_density(rows)
    w = measured_kv_wire_bytes(rows)
    assert d is not None and abs(d - probe["density"]) < 1e-9
    assert w == 2 * probe["wire_bytes"]  # traffic sums, density averages
    assert measured_kv_density([]) is None
    assert measured_kv_wire_bytes([]) is None


def test_wire_metrics_hook_records_density_and_bytes():
    x = _block(11, 4096, 0.5)
    with registry.record_kernel_metrics() as rows:
        packed = kv_pack(x)
    summary = registry.metric_summary(rows)["kv_pack"]
    nnz = int(packed["nnz"])
    assert summary["wire_bytes"] == float(kv_wire_bits(nnz, 4096)) / 8.0
    assert summary["density"] == nnz / 4096
    # inert under jit tracing (no host sync in compiled programs)
    with registry.record_kernel_metrics() as rows2:
        jax.jit(kv_pack)(x)
    assert not [r for r in rows2 if r["op"] == "kv_pack"]


# -- the serving slot pool on a real model cache ------------------------------


def _pool_fixture():
    from repro.configs import get_arch
    from repro.models.lm import lm_init, lm_init_cache

    cfg = get_arch("llama3.2-1b").reduced()
    cache = lm_init_cache(cfg, 2, 24)
    # fill with recognizable non-trivial values: first 9 positions live
    def fill(path, leaf):
        if leaf.ndim < 2:
            return leaf
        live = jnp.arange(leaf.shape[-3 if leaf.ndim >= 4 else -2]) < 9
        shape = [1] * leaf.ndim
        shape[-3 if leaf.ndim >= 4 else -2] = live.shape[0]
        vals = jax.random.normal(jax.random.PRNGKey(hash(str(path)) % 2**31),
                                 leaf.shape).astype(leaf.dtype)
        return jnp.where(live.reshape(shape), vals, jnp.zeros((), leaf.dtype))

    cache = jax.tree_util.tree_map_with_path(fill, cache)
    cache["pos"] = jnp.asarray([9, 9], jnp.int32)
    return cfg, cache


def test_kvpool_roundtrip_is_bit_exact_on_model_cache():
    from repro.serving import kvpool

    _, cache = _pool_fixture()
    pool = kvpool.pack_cache(cache)
    back = kvpool.unpack_cache(pool)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(cache)[0],
            jax.tree_util.tree_flatten_with_path(back)[0]):
        assert str(pa) == str(pb)
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=str(pa))


def test_kvpool_wire_stats_track_occupancy():
    from repro.serving import kvpool

    _, cache = _pool_fixture()
    stats = kvpool.pool_wire_stats(kvpool.pack_cache(cache))
    assert 0.0 < stats["kv_density"] < 0.6  # 9 of 24 positions live
    assert stats["kv_compression_vs_fp32"] > 2.0
    assert stats["kv_wire_bytes"] < stats["kv_dense_fp32_bytes"]


def test_kvpool_release_clears_one_slot_only():
    from repro.serving import kvpool

    _, cache = _pool_fixture()
    pool = kvpool.pack_cache(cache)
    cleared = kvpool.unpack_cache(
        kvpool.pack_cache(
            kvpool.release_slot(kvpool.unpack_cache(pool), jnp.int32(0))))
    for path, leaf in jax.tree_util.tree_flatten_with_path(cleared)[0]:
        name = str(path)
        ax = kvpool.slot_axis(path) if "pos" not in name else 0
        sl = np.asarray(jnp.take(leaf, 0, axis=ax), np.float32)
        keep = np.asarray(jnp.take(leaf, 1, axis=ax), np.float32)
        np.testing.assert_array_equal(sl, np.zeros_like(sl), err_msg=name)
        orig = np.asarray(jnp.take(_lookup_like(cache, path), 1, axis=ax), np.float32)
        np.testing.assert_array_equal(keep, orig, err_msg=name)


def _lookup_like(tree, path):
    node = tree
    for p in path:
        node = node[getattr(p, "key", getattr(p, "idx", None))]
    return node


def test_packed_splice_surgery_matches_dense_path():
    """install_packed / release_packed (the engine's O(slot) splices) are
    bit-identical to packing the dense-path install/release of the whole
    pool — the equivalence that lets the engine skip full-pool repacks."""
    import jax.numpy as jnp

    from repro.serving import kvpool

    cfg, cache = _pool_fixture()
    pool = kvpool.pack_cache(cache)

    # a batch-1 "prefill" cache of length 7 (pool max_len is 24)
    from repro.models.lm import lm_init_cache

    pcache = lm_init_cache(cfg, 1, 7)
    pcache = jax.tree_util.tree_map(
        lambda leaf: jax.random.normal(jax.random.PRNGKey(leaf.size % 97),
                                       leaf.shape).astype(leaf.dtype)
        if hasattr(leaf, "ndim") and leaf.ndim >= 2 else leaf, pcache)

    slot = jnp.int32(1)
    spliced = kvpool.install_packed(pool, pcache, slot, 7)
    via_dense = kvpool.pack_cache(
        kvpool.install_prefill(kvpool.unpack_cache(pool), pcache, slot, 7))
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(spliced)[0],
            jax.tree_util.tree_flatten_with_path(via_dense)[0]):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=str(pa))

    rel = kvpool.release_packed(spliced, jnp.int32(0))
    via_dense_rel = kvpool.pack_cache(
        kvpool.release_slot(kvpool.unpack_cache(spliced), jnp.int32(0)))
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(rel)[0],
            jax.tree_util.tree_flatten_with_path(via_dense_rel)[0]):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=str(pa))
