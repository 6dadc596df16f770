"""GPipe-style pipeline parallelism over a mesh axis (the `pod` axis of
the multi-pod mesh, or a dedicated `stage` axis at larger scales).

DESIGN.md §4 documents why PP is *off by default* at 512 chips (FSDP x TP
fits); this module is the scale-out path past the point where DP axes
saturate (1000+ nodes): layers split into S stages, microbatches stream
through stages via ``jax.lax.ppermute`` inside ``shard_map``, bubbles
amortized by M >> S microbatching.

The implementation is deliberately framework-shaped: it wraps any
per-stage apply function (a stack of blocks) and composes with the data/
model axes left to GSPMD (auto axes), exactly like
``runtime/compression.py`` does for the pod axis.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[jax.Array, any], jax.Array],
    stage_params: any,  # pytree with leading [n_stages] dim, sharded over axis
    x_microbatches: jax.Array,  # (M, mb, ...) microbatched inputs
    *,
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run M microbatches through S pipeline stages on mesh axis ``axis``.

    Schedule: standard GPipe fill-drain over T = M + S - 1 ticks.  At tick
    t, stage s processes microbatch (t - s); inter-stage transfer is a
    ring ppermute.  Returns the stage-(S-1) outputs re-assembled as
    (M, mb, ...).

    Correctness contract (tested): equals sequentially applying the S
    stages to each microbatch.
    """
    n_stages = mesh.shape[axis]
    m = x_microbatches.shape[0]
    assert m >= 1

    def body(params_local, xs_local):
        # params_local: this stage's params (leading dim 1); xs_local: (M, mb, ...)
        sidx = jax.lax.axis_index(axis)
        params_one = jax.tree_util.tree_map(lambda a: a[0], params_local)
        mb_shape = xs_local.shape[1:]
        total = m + n_stages - 1

        def tick(carry, t):
            acc_out, live = carry  # live: the activation entering this stage
            # stage 0 ingests microbatch t (if in range); others use `live`
            mb_idx = jnp.clip(t, 0, m - 1)
            inject = xs_local[mb_idx]
            inp = jnp.where(sidx == 0, inject, live)
            out = stage_fn(inp, params_one)
            # mask ticks where this stage has no valid microbatch yet/anymore
            my_mb = t - sidx
            valid = (my_mb >= 0) & (my_mb < m)
            out = jnp.where(valid, out, jnp.zeros_like(out))
            # last stage records its finished microbatch
            is_last = sidx == n_stages - 1
            write_idx = jnp.clip(my_mb, 0, m - 1)
            acc_out = jax.lax.cond(
                valid & is_last,
                lambda a: a.at[write_idx].set(out),
                lambda a: a,
                acc_out,
            )
            # ring transfer to the next stage
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (acc_out, nxt), None

        acc0 = jnp.zeros((m,) + mb_shape, xs_local.dtype)
        live0 = jnp.zeros(mb_shape, xs_local.dtype)
        (acc_out, _), _ = jax.lax.scan(tick, (acc0, live0), jnp.arange(total))
        # every stage holds garbage except the last; gather and select it
        gathered = jax.lax.all_gather(acc_out, axis)  # (S, M, mb, ...)
        return gathered[n_stages - 1]

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        axis_names={axis},
        check_vma=False,
    )
    return fn(stage_params, x_microbatches)


def stack_stage_params(per_stage_params: list) -> any:
    """[S] list of per-stage param pytrees -> stacked tree (leading S)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage_params)
