"""Sharded serving steps: rows over the data axis, logits packed on wire.

The spring-mesh serving program (DESIGN.md §14) genuinely shards request
rows: each device prefills/decodes its ``batch/world`` rows (per-row
compute is batch-composition-invariant — the engine's alone-vs-strangers
seal), then the per-shard logits cross the wire through
``packed_all_gather`` so every device reassembles the full ``(B, V)``
logit block bit-identically to the single-device oracle.  KV-cache
leaves stay sharded on their batch dim between steps; specs come from
the same ``logical_axes_for_path`` table jit boundary shardings use,
restricted to the ``data`` axis.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import packed_all_gather
from repro.dist.mesh import data_axis_size
from repro.runtime.sharding import logical_to_spec, sharding_context
from repro.runtime.tree_sharding import logical_axes_for_path
from repro.serving.steps import make_decode_step, make_prefill_step

#: shard_map rules for serving: only the data axis participates (pod and
#: model axes stay replicated here); unknown logical axes replicate.  The
#: shard_maps are manual over every axis, since a Pallas (Mosaic) kernel
#: in the body cannot be partitioned over an axis left to the compiler.
DATA_ONLY_RULES: dict[str, tuple] = {
    "batch": (("data",),),
    "cache_batch": (("data",),),
}


def _cache_specs(cache, mesh):
    """PartitionSpec tree for a cache pytree: batch dim over 'data',
    everything else replicated (leading scanned-layer dims handled by
    the path table's None padding)."""
    with sharding_context(mesh, DATA_ONLY_RULES):
        return jax.tree_util.tree_map_with_path(
            lambda p, l: logical_to_spec(
                logical_axes_for_path(p, l.shape), l.shape, mesh),
            cache)


def _row_specs(batch, mesh):
    """PartitionSpec tree sharding dim 0 of every leaf over 'data'."""
    with sharding_context(mesh, DATA_ONLY_RULES):
        return jax.tree_util.tree_map(
            lambda l: logical_to_spec(
                ("batch",) + (None,) * (l.ndim - 1), l.shape, mesh),
            batch)


def _gather_logits(logits, world, impl):
    b_local, vocab = logits.shape
    flat = packed_all_gather(logits.reshape(-1), axis_name="data", impl=impl)
    return flat.reshape(world * b_local, vocab)


def make_sharded_prefill_step(arch, step_cfg, mesh, reduced: bool = False,
                              impl: Optional[str] = None):
    base = make_prefill_step(arch, step_cfg, mesh=None, reduced=reduced)
    world = data_axis_size(mesh)

    def body(params, batch, key):
        logits, cache = base(params, batch, key)
        return _gather_logits(logits, world, impl), cache

    def prefill(params, batch, key):
        _, cache_shape = jax.eval_shape(base, params, batch, key)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), _row_specs(batch, mesh), P()),
            out_specs=(P(), _cache_specs(cache_shape, mesh)),
            check_vma=False,
        )
        return fn(params, batch, key)

    return prefill


def make_sharded_decode_step(arch, step_cfg, mesh, reduced: bool = False,
                             impl: Optional[str] = None):
    base = make_decode_step(arch, step_cfg, mesh=None, reduced=reduced)
    world = data_axis_size(mesh)

    def body(params, tokens, cache, key):
        logits, new_cache = base(params, tokens, cache, key)
        return _gather_logits(logits, world, impl), new_cache

    def decode(params, tokens, cache, key):
        _, cache_shape = jax.eval_shape(base, params, tokens, cache, key)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), _row_specs(tokens, mesh), _cache_specs(cache, mesh),
                      P()),
            out_specs=(P(), _cache_specs(cache_shape, mesh)),
            check_vma=False,
        )
        return fn(params, tokens, cache, key)

    return decode
