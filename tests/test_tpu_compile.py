"""AOT compiles of the main-path Pallas kernels for a described TPU v5e.

Each test lowers one registered ``pallas`` lowering at the real widths the
train and serve paths use (``llama3.2-1b`` MLP matmuls, ``mamba2-780m``'s
in_proj and out_proj matmuls and SSD scan, ``deepseek-v2-lite``'s routed
expert matmuls, one slot of the llama KV pool) and compiles it for one chip of
a ``v5e:2x2`` topology described without the chip.  The TPU compiler
refuses here what it would refuse on the chip: tilings the (8, 128) rule
forbids, casts and reductions Mosaic does not lower, VMEM overuse.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# llama3.2-1b: d_model 2048, d_ff 8192; a 2048-token prefill chunk.
M, D, F = 2048, 2048, 8192
# masked_matmul's (M, K, N): llama's MLP up projection; mamba2-780m's
# in_proj (d_model 1536 -> 6448, which pads to 6528) and out_proj
# (d_inner 3072 -> 1536), at batch 1 x 2048; deepseek-v2-lite's routed
# expert gate/up (d_model 2048 -> d_ff 1408) and down projections over
# an 8192-row dropless buffer (batch 2 x 4096).
MATMULS = {"llama_up": (M, D, F), "mamba_in_proj": (2048, 1536, 6448),
           "mamba_out_proj": (2048, 3072, 1536),
           "expert_up": (8192, 2048, 1408), "expert_down": (8192, 1408, 2048)}
# mamba2-780m: 48 heads of 64, one state group of 128; batch 2 x 1024.
SSD_B, SSD_S, SSD_H, SSD_P, SSD_G, SSD_N = 2, 1024, 48, 64, 1, 128
# the llama K (or V) pool leaf the engine packs every decode tick:
# 16 layers x 4 slots blocks of max_len 161 x 8 kv heads x 64.
KV_BLOCKS, KV_LEN = 16 * 4, 161 * 8 * 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel in the program"
    return compiled


def _pallas_fn(op):
    from repro.kernels import registry

    return registry.impls(op)["pallas"].fn


@pytest.mark.parametrize("mkn", MATMULS.values(), ids=MATMULS.keys())
def test_masked_matmul_forward_compiles(one_chip, no_persistent_cache, mkn):
    m, k, n = mkn
    fn = _pallas_fn("masked_matmul")
    _compile(lambda x, w, s: fn(x, w, s),
             _spec((m, k), jnp.float32, one_chip),
             _spec((k, n), jnp.float32, one_chip),
             _spec((), jnp.uint32, one_chip))


@pytest.mark.parametrize("mkn", MATMULS.values(), ids=MATMULS.keys())
def test_masked_matmul_dx_compiles(one_chip, no_persistent_cache, mkn):
    m, k, n = mkn
    fn = _pallas_fn("masked_matmul_dx")
    _compile(fn, _spec((m, n), jnp.float32, one_chip),
             _spec((k, n), jnp.float32, one_chip))


@pytest.mark.parametrize("mkn", MATMULS.values(), ids=MATMULS.keys())
def test_masked_matmul_dw_compiles(one_chip, no_persistent_cache, mkn):
    m, k, n = mkn
    fn = _pallas_fn("masked_matmul_dw")
    _compile(fn, _spec((m, k), jnp.float32, one_chip),
             _spec((m, n), jnp.float32, one_chip))


def test_stochastic_round_compiles(one_chip, no_persistent_cache):
    fn = _pallas_fn("stochastic_round")
    _compile(fn, _spec((M, F), jnp.float32, one_chip),
             _spec((), jnp.uint32, one_chip))


def test_mask_pack_compiles(one_chip, no_persistent_cache):
    _compile(_pallas_fn("mask_pack"), _spec((M, D), jnp.float32, one_chip))


def test_kv_pack_compiles(one_chip, no_persistent_cache):
    # vmapped over the pool's blocks, as serving/kvpool.py calls it
    _compile(jax.vmap(_pallas_fn("kv_pack")),
             _spec((KV_BLOCKS, KV_LEN), jnp.bfloat16, one_chip))


def test_ssd_scan_compiles(one_chip, no_persistent_cache):
    _compile(_pallas_fn("ssd_scan"),
             _spec((SSD_B, SSD_S, SSD_H, SSD_P), jnp.float32, one_chip),
             _spec((SSD_B, SSD_S, SSD_H), jnp.float32, one_chip),
             _spec((SSD_H,), jnp.float32, one_chip),
             _spec((SSD_B, SSD_S, SSD_G, SSD_N), jnp.float32, one_chip),
             _spec((SSD_B, SSD_S, SSD_G, SSD_N), jnp.float32, one_chip))
