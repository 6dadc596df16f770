"""Mixture-of-Experts FFN with capacity-bounded top-k dispatch (GShard
style), shardable as expert parallelism over the ``model`` mesh axis.

Assigned MoE archs: olmoe-1b-7b (64e, top-8) and deepseek-v2-lite (64
routed top-6 + 2 shared).  Dispatch is scatter/gather with static
capacity ``C = ceil(T * top_k / E) * capacity_factor`` so every shape is
jit-static; tokens overflowing an expert's capacity are dropped (their
combine weight contributes nothing) — standard GShard semantics, recorded
in DESIGN.md.  FLOPs scale with activated capacity, not E, so the
roofline sees the true MoE compute.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.layers import SpringContext, dense_init
from repro.core.spring_ops import spring_matmul
from repro.runtime.sharding import constrain


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int
    top_k: int
    d_ff: int  # per-expert hidden
    n_shared: int = 0  # shared (always-on) experts, deepseek-style
    shared_d_ff: int = 0
    capacity_factor: float = 1.25


def moe_init(key, d: int, spec: MoESpec):
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    e, f = spec.n_experts, spec.d_ff
    scale_in = 1.0 / (d**0.5)
    scale_out = 1.0 / (f**0.5)
    p = {
        "router": dense_init(kr, d, e, scale=0.02),
        "w_gate": jax.random.normal(kg, (e, d, f), jnp.float32) * scale_in,
        "w_up": jax.random.normal(ku, (e, d, f), jnp.float32) * scale_in,
        "w_down": jax.random.normal(kd, (e, f, d), jnp.float32) * scale_out,
    }
    if spec.n_shared:
        from repro.models.layers import swiglu_init

        p["shared"] = swiglu_init(ks, d, spec.shared_d_ff * spec.n_shared)
    return p


def _expert_ffn(buf: jax.Array, params, ctx: SpringContext) -> jax.Array:
    """(E, C, d) -> (E, C, d) batched swiglu through SPRING numerics."""
    w_gate = constrain(params["w_gate"], ("w_experts", "w_embed", None))
    w_up = constrain(params["w_up"], ("w_experts", "w_embed", None))
    w_down = constrain(params["w_down"], ("w_experts", None, "w_embed"))
    if ctx.cfg.mode == "dense":
        dt = ctx.cfg.dense_dtype
        g = jnp.einsum("ecd,edf->ecf", buf.astype(dt), w_gate.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", buf.astype(dt), w_up.astype(dt))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        return jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))
    # quantized path: per-expert spring matmuls via vmap-free reshape
    e, c, d = buf.shape
    f = w_gate.shape[-1]

    def one(args):
        b, wg, wu, wd = args
        g = spring_matmul(b, wg, ctx.cfg, ctx.keys, probe=ctx.tile_probe)
        u = spring_matmul(b, wu, ctx.cfg, ctx.keys, probe=ctx.tile_probe)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * u
        return spring_matmul(h, wd, ctx.cfg, ctx.keys, probe=ctx.tile_probe)

    return jax.lax.map(one, (buf, w_gate, w_up, w_down))


MOE_TOKEN_CHUNK = 32768  # cap dispatch-buffer size at prefill scale


def moe_apply(params, x: jax.Array, ctx: SpringContext, spec: MoESpec):
    """x: (B, S, d) -> (B, S, d), plus aux load-balancing loss.

    Token streams larger than MOE_TOKEN_CHUNK are processed in chunks
    (remat'd, scanned) so the (E, C, d) dispatch buffers never hold the
    k-times-replicated copy of a 1M-token prefill at once.
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
            return moe_apply(params, xc, ctx, spec)

        xs = x.reshape(b, nc, tc, d).swapaxes(0, 1)  # (nc, B, tc, d)
        ys, auxs = jax.lax.map(one, xs)
        y = ys.swapaxes(0, 1).reshape(b, s, d)
        return y, auxs.mean()
    t = b * s
    e, k = spec.n_experts, spec.top_k
    cap = int((t * k / e) * spec.capacity_factor + 0.999)
    cap = max(cap, 4)

    flat = x.reshape(t, d)
    logits = jnp.einsum(
        "td,de->te", flat.astype(jnp.float32), params["router"]["kernel"]
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=0)

    dispatched = jnp.zeros((e, cap, d), flat.dtype)

    # position of each (token, slot) within its expert = assignments before
    # it in flattened token-major order (a static, consistent priority rule)
    onehots = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)  # (T, k, E)
    flat_oh = onehots.reshape(t * k, e)
    pos_all = jnp.cumsum(flat_oh, axis=0) - flat_oh  # (T*k, E)
    pos = jnp.take_along_axis(
        pos_all, gate_idx.reshape(t * k, 1), axis=1
    ).reshape(t, k)
    ce = flat_oh.sum(axis=0).astype(jnp.float32) / (t * k)
    aux_loss = e * jnp.sum(me * ce)

    keep = pos < cap
    safe_pos = jnp.where(keep, pos, cap - 1)
    drop_e = jnp.where(keep, gate_idx, e)  # out-of-range expert -> dropped

    # scatter tokens into (E, C, d)
    dispatched = dispatched.at[drop_e.reshape(-1), safe_pos.reshape(-1)].set(
        jnp.repeat(flat[:, None, :], k, axis=1).reshape(t * k, d), mode="drop"
    )
    dispatched = constrain(dispatched, ("experts_act", "capacity", "embed"))

    out_buf = _expert_ffn(dispatched, params, ctx)  # (E, C, d)
    out_buf = constrain(out_buf, ("experts_act", "capacity", "embed"))

    gathered = out_buf[jnp.where(keep, gate_idx, 0).reshape(-1), safe_pos.reshape(-1)]
    gathered = gathered.reshape(t, k, d).astype(jnp.float32)
    w = jnp.where(keep, gate_vals, 0.0)
    combined = jnp.einsum("tkd,tk->td", gathered, w)
    y = combined.reshape(b, s, d).astype(x.dtype)
    y = constrain(y, ("batch", "seq", "embed"))

    if spec.n_shared:
        from repro.models.layers import swiglu_apply

        y = y + swiglu_apply(params["shared"], x, ctx)
    return y, aux_loss
