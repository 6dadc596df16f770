"""Sharded train step: replicated compute + packed gradient exchange.

The spring-mesh training program (DESIGN.md §14) keeps params, optimizer
state and the batch replicated across the ``data`` axis — every device
runs the identical forward/backward — and splices a *real* packed
reduce-scatter / all-gather round trip into the gradient path via the
``grad_sync`` seam of ``make_train_step``.  Because the per-device
addends are identical and the world is a power of two (RunSpec
validates), the tree sum is exactly ``world·g`` and the ``/world``
rescale is an exponent shift, so the synced gradients — and therefore
the losses — are bit-identical to the single-device oracle while the
gradients genuinely cross the wire binary-mask compressed.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import packed_all_reduce_mean
from repro.dist.mesh import data_axis_size
from repro.runtime.train import make_train_step


def make_sharded_train_step(arch, step_cfg, mesh, reduced: bool = False,
                            impl: Optional[str] = None):
    """Build the shard_map'd train step for an explicit data mesh."""
    if step_cfg.compress_pod_grads:
        raise ValueError(
            "compress_pod_grads drives the int8+EF pod link; the packed "
            "data-axis exchange is a separate link — use shape.mesh.pod "
            "for pods or drop shape.mesh.data")
    world = data_axis_size(mesh)

    def grad_sync(grads):
        # one fused wire transaction: every gradient leaf rides a single
        # packed reduce-scatter -> /world -> all-gather round trip
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        flat = jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in leaves])
        synced = packed_all_reduce_mean(flat, axis_name="data", world=world,
                                        impl=impl)
        out, off = [], 0
        for l in leaves:
            out.append(synced[off:off + l.size].reshape(l.shape).astype(l.dtype))
            off += l.size
        return jax.tree_util.tree_unflatten(treedef, out)

    base = make_train_step(arch, step_cfg, mesh=None, reduced=reduced,
                           grad_sync=grad_sync)

    def step(state, batch):
        in_specs = (
            jax.tree_util.tree_map(lambda _: P(), state),
            jax.tree_util.tree_map(lambda _: P(), batch),
        )
        out_specs = (
            jax.tree_util.tree_map(lambda _: P(), state),
            P(),
        )
        # manual over every mesh axis: a Pallas (Mosaic) kernel in the
        # body cannot be partitioned over an axis left to the compiler
        fn = jax.shard_map(
            base, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return fn(state, batch)

    return step
