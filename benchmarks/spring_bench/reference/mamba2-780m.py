"""Plain float32 reference of mamba2-780m training (Mamba-2, arXiv:2405.21060).

Per layer, pre-norm with a residual:

    h            = RMSNorm(x)
    z, xBC, dt   = split(h @ W_in)                    (d_inner, conv_dim, heads)
    xBC          = silu(causal depthwise conv_4(xBC) + b)
    x_s, B, C    = split(xBC)                         (d_inner, N, N; one group)
    dt           = softplus(dt + dt_bias),  A = -exp(A_log)
    y_t          = sum_{j<=t} (C_t . B_j) exp(cum_t - cum_j) dt_j x_j + D x_t
                   with cum_t = sum_{i<=t} dt_i A       (the SSD quadratic form)
    x           += (RMSNorm(y * silu(z))) @ W_out

then a final RMSNorm and the tied head; the loss is the mean next-token
cross-entropy.  Every product runs at ``Precision.HIGHEST``, the SSD in
causal blocks of queries, each layer recomputed in the backward pass so
that the reference fits beside its own weights.  The optimizer is AdamW
as the training job states it (global-norm clip, linear warmup), on the
host's CPU device.

The weights come from a key, in the parameter layout the program takes
(one stack of layers under ``unit_0``); the same function makes them for
the program and, again, for this reference.  The embedding has the
vocabulary padded to ``pad_vocab_size_multiple`` rows, as mamba_ssm
builds it.
"""

from __future__ import annotations

import math
from pathlib import Path

import jax
import jax.numpy as jnp

from harness import load_module

ops = load_module(Path(__file__).resolve().parent / "_ops.py")

SSD_BLOCK = 512


def embedding_rows(cfg: dict) -> int:
    pad = cfg.get("pad_vocab_size_multiple", 1)
    return -(-cfg["vocab_size"] // pad) * pad


def sizes(cfg: dict) -> dict:
    s = cfg["ssm_cfg"]
    d = cfg["d_model"]
    di = s["expand"] * d
    heads = di // s["headdim"]
    n = s["d_state"] * s["ngroups"]
    return {"d": d, "di": di, "h": heads, "p": s["headdim"], "n": s["d_state"],
            "k": s["d_conv"], "layers": cfg["n_layer"], "vocab": embedding_rows(cfg),
            "conv": di + 2 * n, "proj": 2 * di + 2 * n + heads,
            "eps": cfg["rms_norm_eps"]}


def init_params(cfg: dict, key) -> dict:
    z = sizes(cfg)
    nl, d, di, h = z["layers"], z["d"], z["di"], z["h"]
    k = [jax.random.fold_in(key, i) for i in range(6)]
    dt = jnp.exp(jax.random.uniform(k[4], (nl, h), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "embed": {"embedding": ops.normal(k[0], (z["vocab"], d), 0.02)},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "unit_0": {
            "norm1": {"scale": jnp.ones((nl, d), jnp.float32)},
            "mixer": {
                "in_proj": {"kernel": ops.normal(k[1], (nl, d, z["proj"]), d ** -0.5)},
                "conv_w": ops.normal(k[2], (nl, z["k"], z["conv"]), 0.2),
                "conv_b": jnp.zeros((nl, z["conv"]), jnp.float32),
                "a_log": jnp.log(jax.random.uniform(k[3], (nl, h), jnp.float32, 1.0, 16.0)),
                # the inverse softplus of dt, dt log-uniform in [1e-3, 1e-1]
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "d_skip": jnp.ones((nl, h), jnp.float32),
                "norm": {"scale": jnp.ones((nl, di), jnp.float32)},
                "out_proj": {"kernel": ops.normal(k[5], (nl, di, d), di ** -0.5)},
            },
        },
    }


def _ssd(x, dt, a, b, c, mode):
    """x (S, H, P), dt (S, H), a (H,), b and c (S, N) -> y (S, H, P)."""
    s = x.shape[0]
    cum = jnp.cumsum(dt * a, axis=0)  # (S, H)
    out = []
    for t0 in range(0, s, SSD_BLOCK):
        t1 = min(s, t0 + SSD_BLOCK)  # queries t0..t1 see keys 0..t1
        diff = cum[t0:t1, None, :] - cum[None, :t1, :]  # (tq, tk, H)
        causal = (jnp.arange(t0, t1)[:, None] >= jnp.arange(t1)[None, :])[..., None]
        w = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
        g = ops.matmul(c[t0:t1], b[:t1].T, mode)  # (tq, tk)
        m = g[..., None] * w * dt[None, :t1, :]
        out.append(ops.einsum("tjh,jhp->thp", m, x[:t1], mode))
    return jnp.concatenate(out, axis=0)


def _layer(cfg, mode, x, p):
    z = sizes(cfg)
    s, di, h, pd, n = x.shape[0], z["di"], z["h"], z["p"], z["n"]
    mx = p["mixer"]
    hin = ops.rmsnorm(x, p["norm1"]["scale"], z["eps"])
    zxbcdt = ops.matmul(hin, mx["in_proj"]["kernel"], mode)
    zg, xbc, dt_raw = jnp.split(zxbcdt, [di, di + z["conv"]], axis=-1)
    conv = mx["conv_b"] + sum(
        jnp.pad(xbc, ((z["k"] - 1 - i, 0), (0, 0)))[:s] * mx["conv_w"][i]
        for i in range(z["k"]))
    xbc = jax.nn.silu(conv)
    xs, bm, cm = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt_raw + mx["dt_bias"])
    a = -jnp.exp(mx["a_log"])
    xs = xs.reshape(s, h, pd)
    y = _ssd(xs, dt, a, bm, cm, mode) + mx["d_skip"][None, :, None] * xs
    y = ops.rmsnorm(y.reshape(s, di) * jax.nn.silu(zg), mx["norm"]["scale"], z["eps"])
    return x + ops.matmul(y, mx["out_proj"]["kernel"], mode)


def row_loss_sum(cfg, mode, params, tokens):
    """Sum of the next-token cross-entropies of one row of tokens."""
    z = sizes(cfg)
    emb = params["embed"]["embedding"]
    x = emb[tokens]
    layer = jax.checkpoint(lambda x, p: (_layer(cfg, mode, x, p), None))
    x, _ = jax.lax.scan(layer, x, params["unit_0"])
    x = ops.rmsnorm(x, params["final_norm"]["scale"], z["eps"])
    logits = ops.matmul(x[:-1], emb.T, mode)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(lse - gold)


def _adamw(cur, m, v, g, scale, lr, bc1, bc2, opt):
    """One AdamW step of every leaf, the gradient first scaled by the clip."""
    b1, b2 = jnp.float32(opt["beta1"]), jnp.float32(opt["beta2"])

    def leaf(p, m, v, g):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + jnp.float32(opt["eps"]))
        return p - lr * (upd + jnp.float32(opt["weight_decay"]) * p), m, v

    out = [leaf(*x) for x in zip(*(jax.tree_util.tree_leaves(t) for t in (cur, m, v, g)))]
    treedef = jax.tree_util.tree_structure(cur)
    return tuple(jax.tree_util.tree_unflatten(treedef, list(x)) for x in zip(*out))


def _norms(tree, scale=1.0):
    return [jnp.sqrt(jnp.sum(jnp.square(x * scale))) for x in jax.tree_util.tree_leaves(tree)]


def train_readings(cfg: dict, key, batches: list, opt: dict, mode: str = "f32") -> dict:
    """Three (or len(batches)) AdamW steps from the weights of ``key``.

    Returns each step's loss, the first step's gradient norm per leaf as
    the optimizer takes it (after the global-norm clip) and before the
    clip, and each leaf's change after the last step.  The gradients are
    taken on the default device, one row at a time; the optimizer's state
    and its update live on the host's CPU device, so that the reference
    needs no more device memory than its weights, one row's activations
    and two sets of gradients."""
    params = jax.jit(lambda k: init_params(cfg, k))(key)
    names = list(ops.leaf_norms(params))
    device = next(iter(jax.tree_util.tree_leaves(params)[0].devices()))
    cpu = jax.devices("cpu")[0]
    p0 = jax.device_put(params, cpu)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    cur, m, v = p0, zeros(p0), zeros(p0)
    grad_row = jax.jit(jax.value_and_grad(
        lambda p, t: row_loss_sum(cfg, mode, p, t)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=(0,))
    mean = jax.jit(lambda g, count: jax.tree_util.tree_map(lambda x: x / count, g))
    global_norm = jax.jit(lambda g: jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                                 for x in jax.tree_util.tree_leaves(g))))
    norms = jax.jit(_norms)
    adamw = jax.jit(lambda *a: _adamw(*a, opt), donate_argnums=(1, 2))
    out = {"losses": []}
    for step, tokens in enumerate(batches):
        tokens = jnp.asarray(tokens)
        total, grads = 0.0, None
        for row in tokens:
            ls, g = grad_row(params, row)
            total += float(ls)
            grads = g if grads is None else add(grads, g)
        count = tokens.shape[0] * (tokens.shape[1] - 1)
        out["losses"].append(total / count)
        del params
        g = mean(jax.device_put(grads, cpu), jnp.float32(count))
        del grads
        gn = float(global_norm(g))
        scale = jnp.float32(min(1.0, opt["grad_clip"] / (gn + 1e-9)))
        if step == 0:
            out["raw_grad_norms"] = dict(zip(names, map(float, norms(g))))
            out["grad_norms"] = dict(zip(names, map(float, norms(g, scale))))
        t = step + 1
        lr = opt["lr"] * (min(1.0, t / opt["warmup_steps"]) if opt["warmup_steps"] > 0 else 1.0)
        cur, m, v = adamw(cur, m, v, g, scale, jnp.float32(lr),
                          jnp.float32(1.0 - opt["beta1"] ** t), jnp.float32(1.0 - opt["beta2"] ** t))
        del g
        params = jax.device_put(cur, device)
    moved = jax.jit(lambda a, b: _norms(jax.tree_util.tree_map(jnp.subtract, a, b)))(cur, p0)
    out["change_norms"] = dict(zip(names, map(float, moved)))
    return out
