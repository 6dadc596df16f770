"""Mixture-of-Experts FFN: top-k routing over every expert, computed by the
experts this layer holds, shardable as expert parallelism over the
``model`` mesh axis.

Assigned MoE archs: olmoe-1b-7b (64e, top-8) and deepseek-v2-lite (64
routed top-6 + 2 shared).  The router keeps all ``n_experts`` outputs and
picks top-k over all of them; the layer holds experts ``[0, held)``, its
weights' leading dim (one chip's share of an expert-parallel layer, chip
0's; ``held == n_experts`` is the whole layer).  Only the (token, slot)
pairs routed to a held expert are dispatched, each expert's in token
order; pairs routed to absent experts contribute nothing here.  Shared
experts run on every token.

The dispatch buffer's size is static so every shape is jit-static:

  * training (``dropless``) drops no pair.  In the quantized modes the
    buffer is (held, C, d) with C = T: a token picks an expert at most
    once, and the empty rows are zero 128-tiles that masked_matmul's gate
    skips, forward and backward.  In ``dense`` mode, whose einsums would
    compute every empty row, the held pairs are packed expert after
    expert into T * top_k rows and multiplied by ``lax.ragged_dot``.
  * serving: (held, C, d) with ``C = ceil(T * top_k / E *
    capacity_factor)`` (GShard); pairs past an expert's capacity are
    dropped (DESIGN.md).

Each call returns ``(y, aux, rows)``: the load-balancing loss (unweighted;
``MoESpec.aux_alpha`` weighs it in the LM loss) and ``rows``, float32
``[live, buffer, dropped]``: held pairs dispatched, buffer rows, held
pairs dropped.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.layers import SpringContext, dense_init, swiglu_apply, swiglu_init
from repro.core.spring_ops import spring_matmul
from repro.runtime.sharding import constrain


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int
    top_k: int
    d_ff: int  # per-expert hidden
    n_shared: int = 0  # shared (always-on) experts, deepseek-style
    shared_d_ff: int = 0
    capacity_factor: float = 1.25  # serving only; training is dropless
    norm_topk: bool = True  # renormalise the top-k gates to sum to 1
    aux_alpha: float = 0.01  # weight of the load-balancing loss
    # balance each sequence (DeepSeek's seq_aux) rather than the whole batch
    seq_aux: bool = False


def moe_init(key, d: int, spec: MoESpec, held: int = 0):
    """The router over all ``n_experts``; expert weights for the first
    ``held`` (0: all)."""
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    e, f = held or spec.n_experts, spec.d_ff
    scale_in = 1.0 / (d**0.5)
    scale_out = 1.0 / (f**0.5)
    p = {
        "router": dense_init(kr, d, spec.n_experts, scale=0.02),
        "w_gate": jax.random.normal(kg, (e, d, f), jnp.float32) * scale_in,
        "w_up": jax.random.normal(ku, (e, d, f), jnp.float32) * scale_in,
        "w_down": jax.random.normal(kd, (e, f, d), jnp.float32) * scale_out,
    }
    if spec.n_shared:
        p["shared"] = swiglu_init(ks, d, spec.shared_d_ff * spec.n_shared)
    return p


def _expert_ffn(buf: jax.Array, params, ctx: SpringContext,
                group_sizes: jax.Array | None = None) -> jax.Array:
    """(E, C, d) -> (E, C, d) batched swiglu through SPRING numerics; with
    ``group_sizes`` (dense training), (R, d) -> (R, d) over rows packed
    expert after expert, ``group_sizes[e]`` of them expert e's."""
    w_gate = constrain(params["w_gate"], ("w_experts", "w_embed", None))
    w_up = constrain(params["w_up"], ("w_experts", "w_embed", None))
    w_down = constrain(params["w_down"], ("w_experts", None, "w_embed"))
    if ctx.cfg.mode == "dense":
        dt = ctx.cfg.dense_dtype
        if group_sizes is not None:
            def mm(a, w):
                return jax.lax.ragged_dot(a.astype(dt), w.astype(dt), group_sizes)

            g, u = mm(buf, w_gate), mm(buf, w_up)
            return mm(jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u, w_down)
        g = jnp.einsum("ecd,edf->ecf", buf.astype(dt), w_gate.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", buf.astype(dt), w_up.astype(dt))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        return jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))

    # quantized path: one 2-D spring matmul per expert and weight; each
    # expert is recomputed in the backward pass, so that only its input
    # buffer (not its (C, d_ff) products) is kept for all experts at once
    @jax.checkpoint
    def one(args):
        b, wg, wu, wd = args
        g = spring_matmul(b, wg, ctx.cfg, ctx.keys, probe=ctx.tile_probe)
        u = spring_matmul(b, wu, ctx.cfg, ctx.keys, probe=ctx.tile_probe)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * u
        return spring_matmul(h, wd, ctx.cfg, ctx.keys, probe=ctx.tile_probe)

    return jax.lax.map(one, (buf, w_gate, w_up, w_down))


MOE_TOKEN_CHUNK = 32768  # cap dispatch-buffer size at prefill scale


def balance_loss(probs: jax.Array, gate_idx: jax.Array, rows: int) -> jax.Array:
    """E * sum_e f_e * P_e over each of ``rows`` equal slices of the tokens,
    averaged: f_e the share of top-k slots on expert e, P_e its mean
    router probability (Switch over one slice; DeepSeek's ``seq_aux``
    per sequence).  probs (T, E), gate_idx (T, k)."""
    t, e = probs.shape
    k = gate_idx.shape[1]
    counts = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32).reshape(rows, -1, e).sum(1)
    f = counts / (t // rows * k / e)
    return jnp.mean(jnp.sum(f * probs.reshape(rows, -1, e).mean(1), axis=-1))


def moe_apply(params, x: jax.Array, ctx: SpringContext, spec: MoESpec, *,
              dropless: bool = False):
    """x: (B, S, d) -> (y (B, S, d), aux, rows); see the module docstring.

    Token streams larger than MOE_TOKEN_CHUNK are processed in chunks
    (remat'd, scanned) so the (held, C, d) dispatch buffers never hold the
    whole of a 1M-token prefill at once.
    """
    b, s, d = x.shape
    if b * s > MOE_TOKEN_CHUNK and s % 2 == 0:
        nc = 1
        tc = s
        while b * tc > MOE_TOKEN_CHUNK and tc % 2 == 0:
            tc //= 2
            nc *= 2

        @jax.checkpoint
        def one(xc):
            return moe_apply(params, xc, ctx, spec, dropless=dropless)

        xs = x.reshape(b, nc, tc, d).swapaxes(0, 1)  # (nc, B, tc, d)
        ys, auxs, rows = jax.lax.map(one, xs)
        y = ys.swapaxes(0, 1).reshape(b, s, d)
        return y, auxs.mean(), rows.sum(0)
    t = b * s
    e, k = spec.n_experts, spec.top_k
    held = params["w_gate"].shape[0]
    ragged = dropless and ctx.cfg.mode == "dense"
    cap = t if dropless else max(int((t * k / e) * spec.capacity_factor + 0.999), 4)
    flat = x.reshape(t, d)

    with jax.named_scope("spring_moe_dispatch"):
        logits = jnp.einsum("td,de->te", flat.astype(jnp.float32),
                            params["router"]["kernel"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (T, k)
        if spec.norm_topk:
            gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)
        aux_loss = balance_loss(probs, gate_idx, b if spec.seq_aux else 1)

        # place of each held (token, slot) pair among its expert's: the
        # pairs before it in token-major order (a static priority rule)
        pair_e = gate_idx.reshape(-1)
        is_held = pair_e < held
        onehot = (pair_e[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
        if ragged:
            counts = jnp.sum(onehot, axis=0)
            n_rows, keep = t * k, is_held
            slot = jnp.where(keep, jnp.take(jnp.cumsum(counts) - counts, pair_e, mode="clip")
                             + pos, n_rows)
        else:
            n_rows, keep = held * cap, is_held & (pos < cap)
            slot = jnp.where(keep, pair_e * cap + pos, n_rows)
        live = jnp.sum(keep)
        rows = jnp.stack([live, n_rows, jnp.sum(is_held) - live]).astype(jnp.float32)

        # the buffer's rows: each slot's token id (t where empty) and gate
        slot_tok = jnp.full((n_rows,), t, jnp.int32).at[slot].set(
            jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
        slot_gate = jnp.zeros((n_rows,), jnp.float32).at[slot].set(
            gate_vals.reshape(-1), mode="drop")
        dispatched = jnp.take(flat, slot_tok, axis=0, mode="fill", fill_value=0)
        if not ragged:
            dispatched = constrain(dispatched.reshape(held, cap, d),
                                   ("experts_act", "capacity", "embed"))

    if ragged:
        out_buf = _expert_ffn(dispatched, params, ctx, counts)  # (T * k, d)
    else:
        out_buf = _expert_ffn(dispatched, params, ctx)  # (held, C, d)
        out_buf = constrain(out_buf, ("experts_act", "capacity", "embed"))

    with jax.named_scope("spring_moe_combine"):
        weighted = out_buf.reshape(n_rows, d).astype(jnp.float32) * slot_gate[:, None]
        combined = jnp.zeros((t, d), jnp.float32).at[slot_tok].add(weighted, mode="drop")
        y = constrain(combined.reshape(b, s, d).astype(x.dtype), ("batch", "seq", "embed"))

    if spec.n_shared:
        y = y + swiglu_apply(params["shared"], x, ctx)
    return y, aux_loss, rows
