"""Attention mixers: GQA (full / sliding-window) and DeepSeek MLA.

Two execution regimes:
  * train/prefill — memory-efficient chunked attention (lax.scan over
    query chunks, online accumulation is unnecessary since the full kv is
    visible per chunk; window shapes slice only the live kv band).  On
    TPU the Pallas flash kernel (kernels/flash_attention) is the drop-in;
    the jnp chunked form lowers everywhere and is what the dry-run costs.
  * decode — single new token against a KV cache (dense matvecs).  MLA
    uses the absorbed form: scores and values live in the 512-d latent,
    so the cache is (latent + shared rope key), not per-head k/v.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import (
    SpringContext,
    YarnSpec,
    dense_apply,
    dense_init,
    rmsnorm_apply,
    rmsnorm_init,
    rope_apply,
    yarn_mscale,
)
from repro.runtime.sharding import constrain

Q_CHUNK = 1024


def _pos_vec(pos: jax.Array, b: int) -> jax.Array:
    """Decode position(s) as a (B,) vector.

    The static serving path passes one scalar position for the whole
    batch; the continuous-batching engine passes a per-slot (B,) vector
    (slots sit at different depths mid-flight).  All decode-branch math is
    written against the vector form; a scalar broadcasts to it, so the
    two paths share one lowering and stay bit-identical when every row is
    at the same position.
    """
    return jnp.broadcast_to(pos, (b,)).astype(jnp.int32)


def _row_update(cache_leaf: jax.Array, new: jax.Array, slot_v: jax.Array) -> jax.Array:
    """Write row b's single new entry at seq index ``slot_v[b]``."""
    b = new.shape[0]
    return cache_leaf.at[jnp.arange(b), slot_v].set(
        new[:, 0].astype(cache_leaf.dtype))


def _q8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(batch,seq,head) int8 quantization of cache lines (SPRING P2
    applied to the KV cache: halves decode's HBM floor vs bf16)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0 + 1e-9
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0].astype(jnp.bfloat16)


def _dq8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    causal: bool = True
    window: Optional[int] = None  # sliding-window size (recurrentgemma local)
    qkv_bias: bool = False  # qwen2


def gqa_init(key, d: int, spec: AttnSpec):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d, spec.n_heads * spec.head_dim, bias=spec.qkv_bias),
        "wk": dense_init(kk, d, spec.n_kv_heads * spec.head_dim, bias=spec.qkv_bias),
        "wv": dense_init(kv, d, spec.n_kv_heads * spec.head_dim, bias=spec.qkv_bias),
        "wo": dense_init(ko, spec.n_heads * spec.head_dim, d),
    }


def _chunked_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,
    *,
    causal: bool,
    window: Optional[int],
    q_chunk: int = Q_CHUNK,
    scale: Optional[float] = None,
) -> jax.Array:
    """Dense-math attention, scanned over query chunks to bound memory.

    Peak live intermediate is (B, H, q_chunk, S_kv_band) — for 32k prefill
    at q_chunk=1024 that is ~1/32 of the full score matrix.  The softmax
    scale defaults to 1/sqrt(D).
    """
    b, s, h, d = q.shape
    skv = k.shape[1]  # != s for cross-attention (whisper decoder->encoder)
    dv = v.shape[-1]  # may differ from d (MLA: qk 192, v 128)
    kv_heads = k.shape[2]
    group = h // kv_heads
    if scale is None:
        scale = 1.0 / (d**0.5)
    qc = q_chunk if s % q_chunk == 0 else s  # fall back for odd small seqs
    nchunks = s // qc

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    @jax.checkpoint
    def one_chunk(ci):
        q_blk = jax.lax.dynamic_slice_in_dim(q, ci * qc, qc, axis=1).astype(jnp.float32)
        q_idx = ci * qc + jnp.arange(qc)
        if window is not None:
            # only the last (window + qc) keys can be visible to this chunk
            band = min(skv, window + qc)
            start = jnp.clip(ci * qc + qc - band, 0, skv - band)
            k_blk = jax.lax.dynamic_slice_in_dim(kf, start, band, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(vf, start, band, axis=1)
            k_idx = start + jnp.arange(band)
        else:
            k_blk, v_blk, k_idx = kf, vf, jnp.arange(skv)
        # (B, qc, H, D) x (B, Skv, KV, D) -> (B, H, qc, Skv)
        qh = q_blk.reshape(b, qc, kv_heads, group, d)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qh, k_blk) * scale
        mask = jnp.ones((qc, k_idx.shape[0]), bool)
        if causal:
            mask &= q_idx[:, None] >= k_idx[None, :]
        if window is not None:
            mask &= k_idx[None, :] > q_idx[:, None] - window
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_blk)
        return out.reshape(b, qc, h, dv).astype(q.dtype)

    if nchunks == 1:
        return one_chunk(0)
    outs = jax.lax.map(one_chunk, jnp.arange(nchunks))  # (nc, B, qc, H, Dv)
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h, dv)


def gqa_apply(
    params,
    x: jax.Array,
    ctx: SpringContext,
    spec: AttnSpec,
    positions: jax.Array,
    cache: Optional[dict] = None,
    pos: Optional[jax.Array] = None,
    return_cache: bool = False,
):
    """Full-sequence attention (cache=None) or one-step decode (cache set).

    cache: {"k": (B, S_max, KV, D), "v": ...}; ``pos`` is the scalar decode
    position — the new kv is inserted at ``pos`` (ring-indexed when
    spec.window is set) and the updated cache is returned.
    """
    b, s, d_model = x.shape
    h, kv, d = spec.n_heads, spec.n_kv_heads, spec.head_dim
    q = dense_apply(params["wq"], x, ctx, w_logical=("w_embed", "w_qkv")).reshape(b, s, h, d)
    k = dense_apply(params["wk"], x, ctx, w_logical=("w_embed", "w_qkv")).reshape(b, s, kv, d)
    v = dense_apply(params["wv"], x, ctx, w_logical=("w_embed", "w_qkv")).reshape(b, s, kv, d)
    q = constrain(rope_apply(q, positions, spec.rope_theta), ("batch", "seq", "heads", "head_dim"))
    k = constrain(rope_apply(k, positions, spec.rope_theta), ("batch", "seq", "kv_heads", "head_dim"))

    int8_cache = getattr(ctx, "int8_cache", False) and spec.window is None
    if cache is None:
        # Chunked jnp attention is the default lowering (it is what the
        # dry-run costs); a KernelPolicy pin reroutes the whole pass
        # through the flash_attention registry op (Pallas on TPU,
        # dense-softmax ref / interpret elsewhere).
        imp = ctx.kernel_pinned("flash_attention")
        if imp is not None:
            from repro.kernels.flash_attention.ops import flash_attention

            out = flash_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                causal=spec.causal, window=spec.window, impl=imp,
            ).swapaxes(1, 2).astype(x.dtype)
        else:
            out = _chunked_attention(q, k, v, causal=spec.causal, window=spec.window)
        new_cache = None
        if return_cache and int8_cache:
            kq, ks = _q8(k)
            vq, vs = _q8(v)
            new_cache = {"k_q8": kq, "k_sc": ks, "v_q8": vq, "v_sc": vs}
        elif return_cache:
            # prefill fills the serving cache; window caches are rings with
            # the invariant slot(p) = p % window for any prefill length
            kc, vc = k, v
            if spec.window is not None:
                w = spec.window
                if s >= w:
                    last = jnp.arange(s - w, s)
                    slots = last % w
                    kc = jnp.zeros((b, w) + k.shape[2:], k.dtype).at[:, slots].set(k[:, -w:])
                    vc = jnp.zeros((b, w) + v.shape[2:], v.dtype).at[:, slots].set(v[:, -w:])
                else:
                    kc = jnp.pad(k, ((0, 0), (0, w - s), (0, 0), (0, 0)))
                    vc = jnp.pad(v, ((0, 0), (0, w - s), (0, 0), (0, 0)))
            kn = "k_ring" if spec.window is not None else "k"
            vn = "v_ring" if spec.window is not None else "v"
            new_cache = {kn: constrain(kc.astype(jnp.bfloat16), ("cache_batch", "cache_seq", "cache_heads", "head_dim")),
                         vn: constrain(vc.astype(jnp.bfloat16), ("cache_batch", "cache_seq", "cache_heads", "head_dim"))}
    elif int8_cache:
        assert s == 1
        pos_v = _pos_vec(pos, b)
        kq1, ks1 = _q8(k)
        vq1, vs1 = _q8(v)
        ckq = _row_update(cache["k_q8"], kq1, pos_v)
        cks = _row_update(cache["k_sc"], ks1, pos_v)
        cvq = _row_update(cache["v_q8"], vq1, pos_v)
        cvs = _row_update(cache["v_sc"], vs1, pos_v)
        ckq = constrain(ckq, ("cache_batch", "cache_seq", "cache_heads", "head_dim"))
        cvq = constrain(cvq, ("cache_batch", "cache_seq", "cache_heads", "head_dim"))
        group = h // kv
        qh = q.reshape(b, kv, group, d)
        # scale-factored dequant: the int8->f32 convert feeds the dot
        # directly (fuses on TPU; no dequantized cache buffer)
        scores = jnp.einsum("bkgd,bskd->bkgs", qh.astype(jnp.float32),
                            ckq.astype(jnp.float32))
        scores = scores * jnp.moveaxis(cks.astype(jnp.float32), 1, 2)[:, :, None, :] / (d**0.5)
        valid = jnp.arange(ckq.shape[1])[None, :] <= pos_v[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        pv = p * jnp.moveaxis(cvs.astype(jnp.float32), 1, 2)[:, :, None, :]
        out = jnp.einsum("bkgs,bskd->bkgd", pv, cvq.astype(jnp.float32))
        out = out.reshape(b, 1, h, d).astype(x.dtype)
        new_cache = {"k_q8": ckq, "k_sc": cks, "v_q8": cvq, "v_sc": cvs}
    else:
        assert s == 1, "decode processes one token per step"
        pos_v = _pos_vec(pos, b)
        kn = "k_ring" if spec.window is not None else "k"
        vn = "v_ring" if spec.window is not None else "v"
        s_max = cache[kn].shape[1]
        slot_v = pos_v % s_max if spec.window is not None else pos_v
        ck = _row_update(cache[kn], k, slot_v)
        cv = _row_update(cache[vn], v, slot_v)
        ck = constrain(ck, ("cache_batch", "cache_seq", "cache_heads", "head_dim"))
        cv = constrain(cv, ("cache_batch", "cache_seq", "cache_heads", "head_dim"))
        group = h // kv
        qh = q.reshape(b, kv, group, d)
        scores = jnp.einsum(
            "bkgd,bskd->bkgs", qh.astype(jnp.float32), ck.astype(jnp.float32)
        ) / (d**0.5)
        idx = jnp.arange(s_max)
        if spec.window is not None:
            # ring invariant: slot i holds the latest position p <= pos with
            # p % s_max == i, i.e. p = pos - ((pos - i) mod s_max)
            abs_pos = pos_v[:, None] - jnp.mod(pos_v[:, None] - idx[None, :], s_max)
            valid = (abs_pos >= 0) & (abs_pos > pos_v[:, None] - spec.window)
        else:
            valid = idx[None, :] <= pos_v[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", p, cv.astype(jnp.float32))
        out = out.reshape(b, 1, h, d).astype(x.dtype)
        new_cache = {kn: ck, vn: cv}

    out = dense_apply(
        params["wo"], out.reshape(b, s, h * d), ctx,
        w_logical=("w_qkv", "w_embed"), out_logical=("batch", "seq", "embed"),
    )
    return out, new_cache


def gqa_init_cache(batch: int, spec: AttnSpec, max_len: int, dtype=jnp.bfloat16):
    if dtype == "int8" and spec.window is None:
        return {
            "k_q8": jnp.zeros((batch, max_len, spec.n_kv_heads, spec.head_dim), jnp.int8),
            "k_sc": jnp.zeros((batch, max_len, spec.n_kv_heads), jnp.bfloat16),
            "v_q8": jnp.zeros((batch, max_len, spec.n_kv_heads, spec.head_dim), jnp.int8),
            "v_sc": jnp.zeros((batch, max_len, spec.n_kv_heads), jnp.bfloat16),
        }
    if dtype == "int8":
        dtype = jnp.bfloat16  # ring/window caches stay bf16 (small)
    if spec.window is not None:
        return {
            "k_ring": jnp.zeros((batch, spec.window, spec.n_kv_heads, spec.head_dim), dtype),
            "v_ring": jnp.zeros((batch, spec.window, spec.n_kv_heads, spec.head_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, max_len, spec.n_kv_heads, spec.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, spec.n_kv_heads, spec.head_dim), dtype),
    }


# --------------------------------------------------------------------------
# DeepSeek-V2 Multi-head Latent Attention.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLASpec:
    n_heads: int
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnSpec] = None


def mla_softmax_scale(spec: MLASpec) -> float:
    """(dn + dr)^-0.5, times mscale(factor, mscale_all_dim)^2 under YaRN."""
    scale = (spec.qk_nope_dim + spec.qk_rope_dim) ** -0.5
    if spec.rope_scaling is not None:
        y = spec.rope_scaling
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_rope(x: jax.Array, positions: jax.Array, spec: MLASpec) -> jax.Array:
    return rope_apply(x, positions, spec.rope_theta, yarn=spec.rope_scaling,
                      interleaved=True)


def mla_init(key, d: int, spec: MLASpec):
    kq, kkv, kr, kuk, kuv, ko = jax.random.split(key, 6)
    h = spec.n_heads
    return {
        "wq": dense_init(kq, d, h * (spec.qk_nope_dim + spec.qk_rope_dim)),
        "wdkv": dense_init(kkv, d, spec.kv_lora_rank),
        "kv_norm": rmsnorm_init(spec.kv_lora_rank),
        "wkr": dense_init(kr, d, spec.qk_rope_dim),
        "wuk": dense_init(kuk, spec.kv_lora_rank, h * spec.qk_nope_dim),
        "wuv": dense_init(kuv, spec.kv_lora_rank, h * spec.v_head_dim),
        "wo": dense_init(ko, h * spec.v_head_dim, d),
    }


def mla_apply(
    params,
    x: jax.Array,
    ctx: SpringContext,
    spec: MLASpec,
    positions: jax.Array,
    cache: Optional[dict] = None,
    pos: Optional[jax.Array] = None,
    return_cache: bool = False,
):
    """cache: {"ckv": (B, S, rank), "krope": (B, S, dr)}; pos = decode slot.

    DeepSeek-V2's MLA: the compressed kv is RMS-normed (eps 1e-6) before its
    up-projections (the cache holds the normed latent), rope (YaRN where the
    spec scales it) runs on interleaved pairs, and the softmax scale is
    :func:`mla_softmax_scale`.  Everything between the projections runs
    under ``jax.named_scope("spring_mla_attention")``; the projections
    themselves are spring matmuls."""
    b, s, _ = x.shape
    h, dn, dr, dv = spec.n_heads, spec.qk_nope_dim, spec.qk_rope_dim, spec.v_head_dim
    rank = spec.kv_lora_rank
    scale = mla_softmax_scale(spec)

    q = dense_apply(params["wq"], x, ctx, w_logical=("w_embed", "w_qkv")).reshape(b, s, h, dn + dr)
    ckv = dense_apply(params["wdkv"], x, ctx, w_logical=("w_embed", None))  # (B,S,rank)
    kr = dense_apply(params["wkr"], x, ctx, w_logical=("w_embed", None))  # (B,S,dr)

    with jax.named_scope("spring_mla_attention"):
        qn, qr = q[..., :dn], _mla_rope(q[..., dn:], positions, spec)
        ckv = rmsnorm_apply(params["kv_norm"], ckv)
        krope = _mla_rope(kr[:, :, None, :], positions, spec)[:, :, 0, :]  # shared by heads
        wuk = params["wuk"]["kernel"].reshape(rank, h, dn)
        wuv = params["wuv"]["kernel"].reshape(rank, h, dv)

        if cache is None:
            # prefill: expand latent to per-head keys/values (standard form)
            k_nope = jnp.einsum("bsr,rhd->bshd", ckv.astype(jnp.float32), wuk).astype(x.dtype)
            vh = jnp.einsum("bsr,rhd->bshd", ckv.astype(jnp.float32), wuv).astype(x.dtype)
            k_full = jnp.concatenate([k_nope, jnp.broadcast_to(krope[:, :, None, :], (b, s, h, dr)).astype(x.dtype)], -1)
            q_full = jnp.concatenate([qn, qr], -1)
            out = _chunked_attention(q_full, k_full, vh, causal=True, window=None, scale=scale)
            out = out.reshape(b, s, h * dv)
            new_cache = None
            if return_cache:
                new_cache = {"ckv": ckv.astype(jnp.bfloat16), "krope": krope.astype(jnp.bfloat16)}
        else:
            assert s == 1
            pos_v = _pos_vec(pos, b)
            ck = _row_update(cache["ckv"], ckv, pos_v)
            cr = _row_update(cache["krope"], krope, pos_v)
            # absorbed decode: project q into the latent space, attend in latent
            q_lat = jnp.einsum("bhd,rhd->bhr", qn[:, 0].astype(jnp.float32), wuk)  # (B,H,rank)
            s_lat = jnp.einsum("bhr,bsr->bhs", q_lat, ck.astype(jnp.float32))
            s_rope = jnp.einsum("bhd,bsd->bhs", qr[:, 0].astype(jnp.float32), cr.astype(jnp.float32))
            scores = (s_lat + s_rope) * scale
            valid = jnp.arange(ck.shape[1])[None, :] <= pos_v[:, None]
            scores = jnp.where(valid[:, None, :], scores, -1e30)
            p = jax.nn.softmax(scores, axis=-1)
            ctx_lat = jnp.einsum("bhs,bsr->bhr", p, ck.astype(jnp.float32))
            out = jnp.einsum("bhr,rhd->bhd", ctx_lat, wuv).reshape(b, 1, h * dv).astype(x.dtype)
            new_cache = {"ckv": ck, "krope": cr}

    out = dense_apply(params["wo"], out, ctx, w_logical=("w_qkv", "w_embed"),
                      out_logical=("batch", "seq", "embed"))
    return out, new_cache


def mla_init_cache(batch: int, spec: MLASpec, max_len: int, dtype=jnp.bfloat16):
    return {
        "ckv": jnp.zeros((batch, max_len, spec.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, spec.qk_rope_dim), dtype),
    }
