"""Decoder-LM stack: pattern-composed blocks, scanned layer groups, remat,
chunked cross-entropy.  Covers 9 of the 10 assigned archs (whisper is in
``encdec.py``); internvl2's ViT frontend is a stub that prepends
precomputed patch embeddings (DESIGN.md §5).

Layer composition: a config names a repeating ``pattern_unit`` of
(mixer, ffn) kinds, scanned ``n_units`` times with stacked params (keeps
HLO size and compile time O(unit) instead of O(layers)), plus optional
unrolled ``prefix``/``suffix`` layers for patterns that don't divide the
layer count (recurrentgemma's 38 = 12x(rec,rec,local) + 2, deepseek's
dense first layer).

  mixers: "attn" | "local" | "mla" | "ssm" | "rglru"
  ffns:   "swiglu" | "gelu" | "moe" | "none"
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.core.spring_ops import KeyGen
from repro.memstash.config import MemstashConfig
from repro.memstash.stash import stash_apply
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import AttnSpec, MLASpec
from repro.models.layers import (
    SpringContext,
    dense_apply,
    dense_init,
    embed_apply,
    embed_init,
    gelu_mlp_apply,
    gelu_mlp_init,
    layernorm_apply,
    layernorm_init,
    rmsnorm_apply,
    rmsnorm_init,
    swiglu_apply,
    swiglu_init,
)
from repro.models.moe import MoESpec
from repro.models.recurrent import RGLRUSpec
from repro.models.ssm import SSMSpec
from repro.models.losses import chunked_softmax_xent
from repro.runtime.sharding import constrain


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    d_model: int
    vocab: int
    n_layers: int
    pattern_unit: tuple  # ((mixer, ffn), ...)
    n_units: int
    prefix: tuple = ()
    suffix: tuple = ()
    attn: Optional[AttnSpec] = None
    local_attn: Optional[AttnSpec] = None
    mla: Optional[MLASpec] = None
    ssm: Optional[SSMSpec] = None
    rglru: Optional[RGLRUSpec] = None
    moe: Optional[MoESpec] = None
    d_ff: int = 0
    norm: str = "rms"  # "rms" | "layer"
    # MoE expert share: the layer holds experts [0, experts_held) of
    # moe.n_experts (one chip's share of an expert-parallel layer); 0 = all
    experts_held: int = 0
    mlp_bias: bool = False
    tie_embeddings: bool = False
    vlm_prefix_len: int = 0  # internvl2: image patch positions
    remat: bool = True
    # dry-run cost mode: fully unroll the layer scan so cost_analysis sees
    # every layer (XLA counts while bodies once; DESIGN.md §Roofline note)
    scan_unroll: bool = False
    # §Perf lever: bf16 loss-head matmul (LSE stays fp32)
    bf16_logits: bool = False
    # §Perf lever: remat policy — "full" recomputes everything; "block_io"
    # saves each block's output (skips re-forwarding through the TP
    # collectives and attention in the backward pass, costing one
    # activation per layer of memory); "stash" stores each scan unit's
    # residual input binary-mask compressed and restores it in backward
    # (the memstash subsystem — SPRING's RRAM activation store; applies
    # even with remat=False, since the stash is itself a checkpoint
    # strategy)
    remat_policy: str = "full"
    # set by configs: families where 500k-token full attention is intractable
    supports_long_context: bool = False

    def __post_init__(self):
        n = len(self.prefix) + len(self.pattern_unit) * self.n_units + len(self.suffix)
        assert n == self.n_layers, f"{self.name}: pattern covers {n} != {self.n_layers} layers"


# --------------------------------------------------------------------------
# Single block init/apply.
# --------------------------------------------------------------------------


def _norm_init(cfg: LMConfig):
    return rmsnorm_init(cfg.d_model) if cfg.norm == "rms" else layernorm_init(cfg.d_model)


def _norm_apply(cfg: LMConfig, p, x):
    return rmsnorm_apply(p, x) if cfg.norm == "rms" else layernorm_apply(p, x)


def _no_stats() -> dict:
    """A block's training side numbers: the MoE load-balancing loss
    (unweighted) and ``moe_rows`` ``[live, buffer, dropped]``, summed over
    layers (zero where no MoE runs)."""
    return {"aux": jnp.zeros((), jnp.float32), "moe_rows": jnp.zeros((3,), jnp.float32)}


def _add(a: dict, b: dict) -> dict:
    return jax.tree_util.tree_map(jnp.add, a, b)


def block_init(key, cfg: LMConfig, kind: tuple) -> dict:
    mixer, ffn = kind
    km, kf = jax.random.split(key)
    p: dict[str, Any] = {"norm1": _norm_init(cfg)}
    if mixer == "attn":
        p["mixer"] = attn_mod.gqa_init(km, cfg.d_model, cfg.attn)
    elif mixer == "local":
        p["mixer"] = attn_mod.gqa_init(km, cfg.d_model, cfg.local_attn)
    elif mixer == "mla":
        p["mixer"] = attn_mod.mla_init(km, cfg.d_model, cfg.mla)
    elif mixer == "ssm":
        p["mixer"] = ssm_mod.ssm_init(km, cfg.d_model, cfg.ssm)
    elif mixer == "rglru":
        p["mixer"] = rec_mod.rglru_block_init(km, cfg.d_model, cfg.rglru)
    else:
        raise ValueError(mixer)
    if ffn != "none":
        p["norm2"] = _norm_init(cfg)
        if ffn == "swiglu":
            p["ffn"] = swiglu_init(kf, cfg.d_model, cfg.d_ff)
        elif ffn == "gelu":
            p["ffn"] = gelu_mlp_init(kf, cfg.d_model, cfg.d_ff, bias=cfg.mlp_bias)
        elif ffn == "moe":
            p["ffn"] = moe_mod.moe_init(kf, cfg.d_model, cfg.moe, cfg.experts_held)
        else:
            raise ValueError(ffn)
    return p


def block_apply(
    params,
    x: jax.Array,
    ctx: SpringContext,
    cfg: LMConfig,
    kind: tuple,
    positions: jax.Array,
    cache: Optional[dict] = None,
    pos: Optional[jax.Array] = None,
    return_cache: bool = False,
    dropless: bool = False,
):
    """Pre-norm residual block. Returns (x, new_cache, stats) with stats
    as :func:`_no_stats`; ``dropless`` (training) drops no MoE pair."""
    mixer, ffn = kind
    h = _norm_apply(cfg, params["norm1"], x)
    new_cache = None
    if mixer in ("attn", "local"):
        spec = cfg.attn if mixer == "attn" else cfg.local_attn
        out, new_cache = attn_mod.gqa_apply(params["mixer"], h, ctx, spec, positions, cache, pos, return_cache)
    elif mixer == "mla":
        out, new_cache = attn_mod.mla_apply(params["mixer"], h, ctx, cfg.mla, positions, cache, pos, return_cache)
    elif mixer == "ssm":
        out, new_cache = ssm_mod.ssm_apply(params["mixer"], h, ctx, cfg.ssm, cache, return_cache)
    elif mixer == "rglru":
        out, new_cache = rec_mod.rglru_block_apply(params["mixer"], h, ctx, cfg.rglru, cache, return_cache)
    else:
        raise ValueError(mixer)
    x = (x + out).astype(x.dtype)
    stats = _no_stats()
    if ffn != "none":
        h = _norm_apply(cfg, params["norm2"], x)
        if ffn == "swiglu":
            x = (x + swiglu_apply(params["ffn"], h, ctx)).astype(x.dtype)
        elif ffn == "gelu":
            x = (x + gelu_mlp_apply(params["ffn"], h, ctx)).astype(x.dtype)
        elif ffn == "moe":
            out, aux, rows = moe_mod.moe_apply(params["ffn"], h, ctx, cfg.moe, dropless=dropless)
            x = (x + out).astype(x.dtype)
            stats = {"aux": aux, "moe_rows": rows}
    return constrain(x, ("batch", "seq", "embed")), new_cache, stats


def block_init_cache(cfg: LMConfig, kind: tuple, batch: int, max_len: int, dtype=jnp.bfloat16):
    mixer, _ = kind
    if mixer == "attn":
        return attn_mod.gqa_init_cache(batch, cfg.attn, max_len, dtype)
    if mixer == "local":
        return attn_mod.gqa_init_cache(batch, cfg.local_attn, max_len, dtype)
    if mixer == "mla":
        return attn_mod.mla_init_cache(batch, cfg.mla, max_len,
                                       jnp.bfloat16 if dtype == "int8" else dtype)
    if mixer == "ssm":
        return ssm_mod.ssm_init_cache(batch, cfg.ssm,
                                      jnp.bfloat16 if dtype == "int8" else dtype)
    if mixer == "rglru":
        return rec_mod.rglru_init_cache(batch, cfg.rglru,
                                        jnp.bfloat16 if dtype == "int8" else dtype)
    raise ValueError(mixer)


# --------------------------------------------------------------------------
# Full model.
# --------------------------------------------------------------------------


def lm_init(key, cfg: LMConfig) -> dict:
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.vocab, cfg.d_model),
        "final_norm": _norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab)
    for i, kind in enumerate(cfg.prefix):
        params[f"prefix_{i}"] = block_init(jax.random.fold_in(keys[2], i), cfg, kind)
    for i, kind in enumerate(cfg.suffix):
        params[f"suffix_{i}"] = block_init(jax.random.fold_in(keys[3], i), cfg, kind)
    # scanned groups: one stacked param tree per unit position
    for u, kind in enumerate(cfg.pattern_unit):
        def init_one(i, u=u, kind=kind):
            return block_init(jax.random.fold_in(jax.random.fold_in(keys[4], u), i), cfg, kind)

        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[init_one(i) for i in range(cfg.n_units)]
        ) if cfg.n_units > 0 else None
        params[f"unit_{u}"] = stacked
    return params


def lm_hidden(
    params,
    cfg: LMConfig,
    tokens: jax.Array,
    ctx: SpringContext,
    img_embeds: Optional[jax.Array] = None,
) -> tuple[jax.Array, dict]:
    """Token ids (B, S_text) [+ (B, P, d) image embeds] -> (final hidden,
    stats summed over layers, as :func:`_no_stats`).  This is the training
    forward: MoE layers drop no pair."""
    x = embed_apply(params["embed"], tokens, ctx)
    if cfg.vlm_prefix_len:
        assert img_embeds is not None
        x = jnp.concatenate([img_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    stats = _no_stats()
    for i, kind in enumerate(cfg.prefix):
        x, _, a = block_apply(params[f"prefix_{i}"], x, ctx, cfg, kind, positions, dropless=True)
        stats = _add(stats, a)
    if cfg.n_units > 0:
        # scan over units; each scan step applies the unit's kinds in order
        # (so interleaved patterns like (rec, rec, local) keep layer order)
        def body(carry, unit_params):
            h, aux_c = carry
            for u, kind in enumerate(cfg.pattern_unit):
                h, _, a = block_apply(unit_params[u], h, ctx, cfg, kind, positions,
                                      dropless=True)
                h = checkpoint_name(h, "block_out")
                aux_c = _add(aux_c, a)
            return (h, aux_c), None

        # memstash resolution: remat_policy="stash" nominates the residual
        # stream as a stash point, but the MemstashConfig still has the
        # last word (per_layer overrides / min_elems / policy "none"),
        # mirroring how the CNN path routes through ctx.stash_policy
        scfg = ctx.memstash if ctx.memstash is not None else MemstashConfig(policy="stash")
        stash_policy = (scfg.policy_for("lm/residual", int(x.size))
                        if cfg.remat_policy == "stash" else "none")

        if cfg.remat and cfg.remat_policy == "block_io":
            policy = jax.checkpoint_policies.save_only_these_names("block_out")
            body_fn = jax.checkpoint(body, policy=policy)
        elif stash_policy == "stash":
            # memstash: the unit's residual-stream input is stored
            # binary-mask compressed and restored for the backward
            # recompute (dense LM residuals degrade gracefully toward
            # the 20-vs-32-bit value width; see DESIGN.md §4.3).
            # Active regardless of cfg.remat — the stash *is* the
            # checkpointing strategy (compressed-input remat).  Every
            # traced value the unit needs (positions, SR key, tile probe)
            # must flow through aux, not the closure: custom_vjp backward re-traces
            # inside the scan transpose, where closure-captured tracers
            # from the forward trace would leak as jaxpr consts.
            # draw a fresh subkey for the scanned units: reusing the base
            # key would replay the exact folds embed/prefix SR sites
            # already consumed (correlated rounding noise)
            base_key = ctx.keys.next() if ctx.keys is not None else None

            def body_fn(carry, unit_params):
                h, aux_c = carry

                def unit(h_, aux):
                    aux_cc, up, pos, k, probe = aux
                    ctx_u = dataclasses.replace(
                        ctx, keys=KeyGen(k) if k is not None else ctx.keys,
                        tile_probe=probe)
                    for u, kind in enumerate(cfg.pattern_unit):
                        h_, _, a = block_apply(up[u], h_, ctx_u, cfg, kind, pos,
                                               dropless=True)
                        h_ = checkpoint_name(h_, "block_out")
                        aux_cc = _add(aux_cc, a)
                    return h_, aux_cc

                return stash_apply(unit, scfg, "lm/residual", h,
                                   (aux_c, unit_params, positions, base_key,
                                    ctx.tile_probe)), None
        elif cfg.remat or stash_policy == "remat":
            body_fn = jax.checkpoint(body)
        else:
            body_fn = body
        unit_stack = tuple(params[f"unit_{u}"] for u in range(len(cfg.pattern_unit)))
        (x, stats), _ = jax.lax.scan(body_fn, (x, stats), unit_stack,
                                     unroll=cfg.n_units if cfg.scan_unroll else 1)
    for i, kind in enumerate(cfg.suffix):
        x, _, a = block_apply(params[f"suffix_{i}"], x, ctx, cfg, kind, positions, dropless=True)
        stats = _add(stats, a)
    x = _norm_apply(cfg, params["final_norm"], x)
    return x, stats


def _logits_kernel(params, cfg: LMConfig):
    if cfg.tie_embeddings:
        return params["embed"]["embedding"].T  # (d, V)
    return params["lm_head"]["kernel"]


def lm_loss(
    params,
    cfg: LMConfig,
    tokens: jax.Array,
    ctx: SpringContext,
    img_embeds: Optional[jax.Array] = None,
) -> tuple[jax.Array, dict]:
    """Next-token CE, chunked over the sequence so the (tokens x vocab)
    logits tensor never materializes whole (DESIGN.md §4), plus the MoE
    load-balancing loss weighted by ``moe.aux_alpha``.  The metrics hold
    ``ce``, ``aux`` and, for MoE configs, ``moe_rows``."""
    h, stats = lm_hidden(params, cfg, tokens, ctx, img_embeds)
    if cfg.vlm_prefix_len:
        h = h[:, cfg.vlm_prefix_len :]  # loss over text positions only
    b, s, d = h.shape
    inputs_h = h[:, :-1]
    labels = tokens[:, 1:]
    n = s - 1
    total = chunked_softmax_xent(
        inputs_h, labels, _logits_kernel(params, cfg),
        logits_dtype=jnp.bfloat16 if cfg.bf16_logits else jnp.float32)
    ce = total / (b * n)
    if cfg.moe is None:
        return ce, {"ce": ce, "aux": stats["aux"]}
    loss = ce + cfg.moe.aux_alpha * stats["aux"]
    return loss, {"ce": ce, **stats}


# --------------------------------------------------------------------------
# Serving: cache init + single-token decode step.
# --------------------------------------------------------------------------


def lm_init_cache(cfg: LMConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    """dtype may be the string "int8" for quantized full-attention caches
    (other cache kinds fall back to bf16)."""
    cache: dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    for i, kind in enumerate(cfg.prefix):
        cache[f"prefix_{i}"] = block_init_cache(cfg, kind, batch, max_len, dtype)
    for i, kind in enumerate(cfg.suffix):
        cache[f"suffix_{i}"] = block_init_cache(cfg, kind, batch, max_len, dtype)
    for u, kind in enumerate(cfg.pattern_unit):
        one = block_init_cache(cfg, kind, batch, max_len, dtype)
        cache[f"unit_{u}"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_units,) + a.shape).copy(), one
        )
    return cache


def lm_decode_step(
    params,
    cfg: LMConfig,
    tokens: jax.Array,  # (B,) next-token ids
    cache: dict,
    ctx: SpringContext,
) -> tuple[jax.Array, dict]:
    """One decode step: returns (logits (B, V), updated cache).

    ``cache["pos"]`` may be a scalar (static serving: the whole batch sits
    at one depth) or a (B,) vector (continuous batching: each slot at its
    own depth).  The two lower to the same per-row math — a scalar is
    broadcast — so the engine and the static path stay bit-identical.
    """
    pos = cache["pos"]
    x = embed_apply(params["embed"], tokens[:, None], ctx)
    b = x.shape[0]
    positions = jnp.broadcast_to(
        jnp.reshape(pos, (-1, 1)) if getattr(pos, "ndim", 0) else pos,
        (b, 1)).astype(jnp.int32)
    new_cache: dict[str, Any] = {"pos": pos + 1}
    for i, kind in enumerate(cfg.prefix):
        x, c, _ = block_apply(params[f"prefix_{i}"], x, ctx, cfg, kind, positions,
                              cache[f"prefix_{i}"], pos)
        new_cache[f"prefix_{i}"] = c
    if cfg.n_units > 0:
        def body(h, scanned):
            unit_params, unit_caches = scanned
            new_cs = []
            for u, kind in enumerate(cfg.pattern_unit):
                h, c, _ = block_apply(unit_params[u], h, ctx, cfg, kind, positions,
                                      unit_caches[u], pos)
                new_cs.append(c)
            return h, tuple(new_cs)

        unit_params = tuple(params[f"unit_{u}"] for u in range(len(cfg.pattern_unit)))
        unit_caches = tuple(cache[f"unit_{u}"] for u in range(len(cfg.pattern_unit)))
        x, new_cs = jax.lax.scan(body, x, (unit_params, unit_caches),
                                 unroll=cfg.n_units if cfg.scan_unroll else 1)
        for u in range(len(cfg.pattern_unit)):
            new_cache[f"unit_{u}"] = new_cs[u]
    for i, kind in enumerate(cfg.suffix):
        x, c, _ = block_apply(params[f"suffix_{i}"], x, ctx, cfg, kind, positions,
                              cache[f"suffix_{i}"], pos)
        new_cache[f"suffix_{i}"] = c
    x = _norm_apply(cfg, params["final_norm"], x)
    logits = jnp.einsum(
        "bd,dv->bv", x[:, 0].astype(jnp.float32),
        constrain(_logits_kernel(params, cfg), ("w_embed", "w_vocab")).astype(jnp.float32),
    )
    return logits, new_cache


def lm_prefill(
    params,
    cfg: LMConfig,
    tokens: jax.Array,
    ctx: SpringContext,
    img_embeds: Optional[jax.Array] = None,
) -> tuple[jax.Array, dict]:
    """Inference prefill: full forward emitting the serving cache + the
    last-position logits (the production prefill -> decode handoff)."""
    x = embed_apply(params["embed"], tokens, ctx)
    if cfg.vlm_prefix_len:
        assert img_embeds is not None
        x = jnp.concatenate([img_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cache: dict[str, Any] = {"pos": jnp.asarray(s, jnp.int32)}
    for i, kind in enumerate(cfg.prefix):
        x, c, _ = block_apply(params[f"prefix_{i}"], x, ctx, cfg, kind, positions,
                              return_cache=True)
        cache[f"prefix_{i}"] = c
    if cfg.n_units > 0:
        def body(h, unit_params):
            cs = []
            for u, kind in enumerate(cfg.pattern_unit):
                h, c, _ = block_apply(unit_params[u], h, ctx, cfg, kind, positions,
                                      return_cache=True)
                cs.append(c)
            return h, tuple(cs)

        unit_stack = tuple(params[f"unit_{u}"] for u in range(len(cfg.pattern_unit)))
        x, all_cs = jax.lax.scan(body, x, unit_stack,
                                 unroll=cfg.n_units if cfg.scan_unroll else 1)
        for u in range(len(cfg.pattern_unit)):
            cache[f"unit_{u}"] = all_cs[u]
    for i, kind in enumerate(cfg.suffix):
        x, c, _ = block_apply(params[f"suffix_{i}"], x, ctx, cfg, kind, positions,
                              return_cache=True)
        cache[f"suffix_{i}"] = c
    x = _norm_apply(cfg, params["final_norm"], x)
    logits = jnp.einsum(
        "bd,dv->bv", x[:, -1].astype(jnp.float32),
        constrain(_logits_kernel(params, cfg), ("w_embed", "w_vocab")).astype(jnp.float32),
    )
    return logits, cache


# seq-axis position (from the end) of each cache leaf kind, for padding
_CACHE_SEQ_AXIS = {"k": -3, "v": -3, "ckv": -2, "krope": -2,
                   "k_q8": -3, "v_q8": -3, "k_sc": -2, "v_sc": -2}


def pad_cache(cache: dict, extra: int) -> dict:
    """Grow attention caches by ``extra`` decode slots (prefill builds
    caches sized to the prompt; decoding needs headroom).  State caches
    (ssm/conv/rglru) are O(1) and pass through; ring (window) caches keep
    their fixed size."""
    if extra <= 0:
        return cache

    def one(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        leaf_name = names[-1] if names else ""
        ax = _CACHE_SEQ_AXIS.get(leaf_name)
        if ax is None or not hasattr(leaf, "ndim"):
            return leaf
        pads = [(0, 0)] * leaf.ndim
        pads[leaf.ndim + ax] = (0, extra)
        return jnp.pad(leaf, pads)

    return jax.tree_util.tree_map_with_path(one, cache)
