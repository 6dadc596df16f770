"""Plain float32 reference of DeepSeek-V2-Lite training (arXiv:2405.04434),
at one chip's share of an expert-parallel layer.

Per layer, pre-norm with residuals (``RMSNorm`` with ``rms_norm_eps``):

    MLA (no q compression)
      q            = h @ W_q                       per head: nope (dn), rope (dr)
      c            = RMSNorm(h @ W_dkv)            the kv latent (kv_a_layernorm)
      k_rope       = h @ W_kr                      one rope key shared by heads
      k_nope, v    = c @ W_uk, c @ W_uv            per head
      rope         YaRN frequencies (rope_scaling), cos and sin scaled by
                   mscale(f, mscale) / mscale(f, mscale_all_dim), applied as
                   the published apply_rotary_pos_emb: each head's pairs
                   (2i, 2i+1) de-interleaved, then rotate_half
      attention    causal softmax((q_nope.k_nope + q_rope.k_rope) * scale) v
                   with scale = (dn + dr)^-0.5 * mscale(f, mscale_all_dim)^2
      x           += attn @ W_o
    FFN
      layers < first_k_dense_replace: SwiGLU of intermediate_size
      the rest: shared experts (one SwiGLU of n_shared * moe_intermediate
      wide) on every token, plus routed experts: softmax over the router's
      published n_routed_experts outputs, top num_experts_per_tok
      (renormalised only where norm_topk_prob), routed_scaling_factor 1;
      each held expert's SwiGLU output weighted by its gate.

then a final RMSNorm and the untied head.  The loss is the mean next-token
cross-entropy plus ``aux_loss_alpha`` times the per-sequence balance loss
(``seq_aux``: for each sequence, sum_e f_e * P_e with f_e the share of
that sequence's top-k slots on expert e over 1/E and P_e its mean router
probability; averaged over the sequences), summed over the MoE layers.

Departures from the published model, all the cut of the configuration
file: ``num_hidden_layers`` layers, the vocabulary's first ``vocab_size``
rows (ids drawn from them, the loss over them), and only the routed
experts this chip holds (``n_routed_experts`` of the router's
``published.n_routed_experts``, experts 0..held-1): what the absent
experts would add to a token is left out, as on the chip.  Routing is over
all experts.

Every product runs at ``Precision.HIGHEST``; attention in blocks of
queries; each layer recomputed in the backward pass so that the reference
fits beside its own weights.  The weights come from a key in the
parameter layout the program takes (``prefix_<i>`` for the dense layers,
one stack of MoE layers under ``unit_0``); the same function makes them
for the program and, again, for this reference.  The optimizer is AdamW
as the training job states it (global-norm clip, linear warmup), on the
host's CPU device.
"""

from __future__ import annotations

import functools
import math
from pathlib import Path

import jax
import jax.numpy as jnp

from harness import load_module

ops = load_module(Path(__file__).resolve().parent / "_ops.py")

Q_BLOCK = 512


def sizes(cfg: dict) -> dict:
    n_dense = cfg["first_k_dense_replace"]
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"], "dense": n_dense,
            "moe": cfg["num_hidden_layers"] - n_dense, "h": cfg["num_attention_heads"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "ff": cfg["intermediate_size"], "eff": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "held": cfg["n_routed_experts"], "experts": cfg["published"]["n_routed_experts"],
            "k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
            "eps": cfg["rms_norm_eps"]}


def _dense(key, n_in, n_out, lead=()):
    return {"kernel": ops.normal(key, lead + (n_in, n_out), n_in ** -0.5)}


def _mla_params(key, z, lead=()):
    k = [jax.random.fold_in(key, i) for i in range(6)]
    return {
        "wq": _dense(k[0], z["d"], z["h"] * (z["dn"] + z["dr"]), lead),
        "wdkv": _dense(k[1], z["d"], z["rank"], lead),
        "kv_norm": {"scale": jnp.ones(lead + (z["rank"],), jnp.float32)},
        "wkr": _dense(k[2], z["d"], z["dr"], lead),
        "wuk": _dense(k[3], z["rank"], z["h"] * z["dn"], lead),
        "wuv": _dense(k[4], z["rank"], z["h"] * z["dv"], lead),
        "wo": _dense(k[5], z["h"] * z["dv"], z["d"], lead),
    }


def _swiglu_params(key, d, ff, lead=()):
    k = [jax.random.fold_in(key, i) for i in range(3)]
    return {"gate": _dense(k[0], d, ff, lead), "up": _dense(k[1], d, ff, lead),
            "down": _dense(k[2], ff, d, lead)}


def init_params(cfg: dict, key) -> dict:
    z = sizes(cfg)
    d, n = z["d"], z["moe"]
    k = [jax.random.fold_in(key, i) for i in range(8)]
    ones = lambda lead: {"scale": jnp.ones(lead + (d,), jnp.float32)}  # noqa: E731
    params = {
        "embed": {"embedding": ops.normal(k[0], (z["vocab"], d), 0.02)},
        "final_norm": ones(()),
        "lm_head": _dense(k[1], d, z["vocab"]),
        "unit_0": {
            "norm1": ones((n,)), "norm2": ones((n,)),
            "mixer": _mla_params(k[2], z, (n,)),
            "ffn": {
                "router": _dense(k[3], d, z["experts"], (n,)),
                "w_gate": ops.normal(k[4], (n, z["held"], d, z["eff"]), d ** -0.5),
                "w_up": ops.normal(k[5], (n, z["held"], d, z["eff"]), d ** -0.5),
                "w_down": ops.normal(k[6], (n, z["held"], z["eff"], d), z["eff"] ** -0.5),
                "shared": _swiglu_params(jax.random.fold_in(k[7], 0), d, z["shared"], (n,)),
            },
        },
    }
    for i in range(z["dense"]):
        ki = jax.random.fold_in(k[7], 1 + i)
        params[f"prefix_{i}"] = {
            "norm1": ones(()), "norm2": ones(()),
            "mixer": _mla_params(jax.random.fold_in(ki, 0), z),
            "ffn": _swiglu_params(jax.random.fold_in(ki, 1), d, z["ff"]),
        }
    return params


# -- rope ----------------------------------------------------------------------


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(cfg: dict) -> float:
    r = cfg["rope_scaling"]
    m = yarn_get_mscale(r["factor"], r["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope_tables(cfg: dict, seq: int):
    """(cos, sin), each (seq, dr), of DeepseekV2YarnRotaryEmbedding."""
    r, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]

    def correction_dim(rotations):
        return dim * math.log(r["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(r["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(r["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    freq_inter = freq_extra / r["factor"]
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1.0 - mask) + freq_extra * mask
    freqs = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    m = yarn_get_mscale(r["factor"], r["mscale"]) / yarn_get_mscale(r["factor"], r["mscale_all_dim"])
    return jnp.cos(emb) * m, jnp.sin(emb) * m


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x, cos, sin):
    """x (..., S, dr): de-interleave each (2i, 2i+1) pair, then rotate."""
    *lead, s, d = x.shape
    x = x.reshape(*lead, s, d // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(*lead, s, d)
    return x * cos + _rotate_half(x) * sin


# -- layers --------------------------------------------------------------------


def _mla(cfg, mode, x, p, cos, sin):
    z = sizes(cfg)
    s, h, dn, dr, dv = x.shape[0], z["h"], z["dn"], z["dr"], z["dv"]
    q = ops.matmul(x, p["wq"]["kernel"], mode).reshape(s, h, dn + dr)
    q_nope = q[..., :dn]
    q_rope = apply_rope(jnp.swapaxes(q[..., dn:], 0, 1), cos, sin)  # (h, s, dr)
    c = ops.rmsnorm(ops.matmul(x, p["wdkv"]["kernel"], mode), p["kv_norm"]["scale"], z["eps"])
    k_rope = apply_rope(ops.matmul(x, p["wkr"]["kernel"], mode), cos, sin)  # (s, dr)
    k_nope = ops.matmul(c, p["wuk"]["kernel"], mode).reshape(s, h, dn)
    v = ops.matmul(c, p["wuv"]["kernel"], mode).reshape(s, h, dv)
    scale = softmax_scale(cfg)

    @functools.partial(jax.checkpoint, static_argnums=(5,))
    def block(q_nope, q_rope, k_nope, k_rope, v, t0):
        t1 = t0 + q_nope.shape[0]  # queries t0..t1 see keys 0..t1
        sc = ops.einsum("qhd,khd->hqk", q_nope, k_nope, mode) \
            + ops.einsum("hqd,kd->hqk", q_rope, k_rope, mode)
        causal = jnp.arange(t0, t1)[:, None] >= jnp.arange(t1)[None, :]
        pr = jax.nn.softmax(jnp.where(causal[None], sc * scale, -jnp.inf), axis=-1)
        return ops.einsum("hqk,khd->qhd", pr, v, mode)

    out = []
    for t0 in range(0, s, Q_BLOCK):
        t1 = min(s, t0 + Q_BLOCK)
        out.append(block(q_nope[t0:t1], q_rope[:, t0:t1], k_nope[:t1], k_rope[:t1],
                         v[:t1], t0))
    att = jnp.concatenate(out, axis=0).reshape(s, h * dv)
    return ops.matmul(att, p["wo"]["kernel"], mode)


def _swiglu(x, gate, up, down, mode):
    return ops.matmul(jax.nn.silu(ops.matmul(x, gate, mode)) * ops.matmul(x, up, mode),
                      down, mode)


def moe(cfg: dict, mode: str, x, p):
    """The routed and shared experts of one MoE layer on one sequence x (S,
    d): ``(y, balance)``, the experts held being those of ``p``'s weights."""
    z = sizes(cfg)
    s, e, k = x.shape[0], z["experts"], z["k"]
    probs = jax.nn.softmax(ops.matmul(x, p["router"]["kernel"], mode), axis=-1)  # (S, E)
    vals, idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    vals = vals * cfg["routed_scaling_factor"]
    held = p["w_gate"].shape[0]
    gates = jnp.sum(vals[:, :, None] * (idx[:, :, None] == jnp.arange(held)), axis=1)  # (S, held)

    def expert(y, args):
        gate, wg, wu, wd = args
        return y + gate[:, None] * _swiglu(x, wg, wu, wd, mode), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (gates.T, p["w_gate"], p["w_up"], p["w_down"]))
    sh = p["shared"]
    y = y + _swiglu(x, sh["gate"]["kernel"], sh["up"]["kernel"], sh["down"]["kernel"], mode)
    f = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1)) / (s * k / e)
    return y, jnp.sum(f * jnp.mean(probs, axis=0))


def _layer(cfg, mode, x, p, cos, sin, moe_layer: bool):
    z = sizes(cfg)
    x = x + _mla(cfg, mode, ops.rmsnorm(x, p["norm1"]["scale"], z["eps"]), p["mixer"], cos, sin)
    h = ops.rmsnorm(x, p["norm2"]["scale"], z["eps"])
    if moe_layer:
        y, balance = moe(cfg, mode, h, p["ffn"])
        return x + y, balance
    f = p["ffn"]
    return x + _swiglu(h, f["gate"]["kernel"], f["up"]["kernel"], f["down"]["kernel"], mode), 0.0


def hidden(cfg: dict, mode: str, params, tokens):
    """Final hidden states (S, d) of one row of tokens and its balance loss
    summed over the MoE layers."""
    z = sizes(cfg)
    cos, sin = rope_tables(cfg, tokens.shape[0])
    x = params["embed"]["embedding"][tokens]
    for i in range(z["dense"]):
        x, _ = jax.checkpoint(lambda x, p: _layer(cfg, mode, x, p, cos, sin, False))(
            x, params[f"prefix_{i}"])
    layer = jax.checkpoint(lambda x, p: _layer(cfg, mode, x, p, cos, sin, True))
    x, balances = jax.lax.scan(layer, x, params["unit_0"])
    return ops.rmsnorm(x, params["final_norm"]["scale"], z["eps"]), jnp.sum(balances)


def logits(cfg: dict, mode: str, params, tokens):
    x, _ = hidden(cfg, mode, params, tokens)
    return ops.matmul(x, params["lm_head"]["kernel"], mode)


def row_loss(cfg, mode, params, tokens, count: int, rows: int):
    """One row's share of the step's loss: its next-token cross-entropies
    summed over ``count`` (the tokens predicted in the batch), plus
    ``aux_loss_alpha`` times its balance loss over ``rows``."""
    x, balance = hidden(cfg, mode, params, tokens)
    lg = ops.matmul(x[:-1], params["lm_head"]["kernel"], mode)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(lse - gold) / count + cfg["aux_loss_alpha"] * balance / rows


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
    and its update live on the host's CPU device."""
    params = jax.jit(lambda k: init_params(cfg, k))(key)
    names = list(ops.leaf_norms(params))
    device = next(iter(jax.tree_util.tree_leaves(params)[0].devices()))
    cpu = jax.devices("cpu")[0]
    p0 = jax.device_put(params, cpu)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    cur, m, v = p0, zeros(p0), zeros(p0)
    grad_row = jax.jit(jax.value_and_grad(
        lambda p, t, count, rows: row_loss(cfg, mode, p, t, count, rows)),
        static_argnums=(2, 3))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=(0,))
    global_norm = jax.jit(lambda g: jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                                 for x in jax.tree_util.tree_leaves(g))))
    norms = jax.jit(_norms)
    adamw = jax.jit(lambda *a: _adamw(*a, opt), donate_argnums=(1, 2))
    out = {"losses": []}
    for step, tokens in enumerate(batches):
        tokens = jnp.asarray(tokens)
        rows, count = tokens.shape[0], tokens.shape[0] * (tokens.shape[1] - 1)
        total, grads = 0.0, None
        for row in tokens:
            ls, g = grad_row(params, row, count, rows)
            total += float(ls)
            grads = g if grads is None else add(grads, g)
        out["losses"].append(total)
        del params
        g = jax.device_put(grads, cpu)
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
