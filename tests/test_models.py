"""Per-architecture smoke tests (reduced configs) + decode-vs-teacher-forced
consistency — one forward/train step on CPU asserting shapes and no NaNs,
as required per assigned arch."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.core.spring_ops import QUANT, KeyGen
from repro.models import encdec as ed_mod
from repro.models import lm as lm_mod
from repro.models.layers import SpringContext

ALL_ARCHS = sorted(ARCHS)


def _finite_tree(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree_util.tree_leaves(tree)
               if jnp.issubdtype(x.dtype, jnp.floating))


@pytest.mark.parametrize("arch_id", ALL_ARCHS)
def test_arch_smoke_forward_and_train_step(arch_id):
    arch = ARCHS[arch_id]
    cfg = arch.reduced()
    key = jax.random.PRNGKey(0)
    ctx = SpringContext()
    B, S = 2, 32
    if arch.is_encdec:
        params = ed_mod.encdec_init(key, cfg)
        frames = jax.random.normal(key, (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
        loss, metrics = ed_mod.encdec_loss(params, cfg, frames, tokens, ctx)
        grads = jax.grad(lambda p: ed_mod.encdec_loss(p, cfg, frames, tokens, ctx)[0])(params)
    else:
        params = lm_mod.lm_init(key, cfg)
        tokens = jax.random.randint(key, (B, S - cfg.vlm_prefix_len), 0, cfg.vocab)
        img = (jax.random.normal(key, (B, cfg.vlm_prefix_len, cfg.d_model), jnp.bfloat16)
               if cfg.vlm_prefix_len else None)
        h, _ = lm_mod.lm_hidden(params, cfg, tokens, ctx, img)
        assert h.shape == (B, S, cfg.d_model)
        loss, metrics = lm_mod.lm_loss(params, cfg, tokens, ctx, img)
        grads = jax.grad(lambda p: lm_mod.lm_loss(p, cfg, tokens, ctx, img)[0])(params)
    assert loss.shape == () and bool(jnp.isfinite(loss))
    assert _finite_tree(grads), f"{arch_id}: non-finite grads"


@pytest.mark.parametrize("arch_id", [a for a in ALL_ARCHS if not ARCHS[a].is_encdec])
def test_decode_matches_teacher_forced(arch_id):
    """Prefill(s-1 tokens) + decode(1) must reproduce the full-sequence
    last-token logits — the KV-cache/state machinery is exact."""
    arch = ARCHS[arch_id]
    cfg = arch.reduced()
    if cfg.vlm_prefix_len:
        pytest.skip("vlm decode covered via llama-family; prefix handling differs")
    key = jax.random.PRNGKey(1)
    ctx = SpringContext()
    B, S = 2, 24
    params = lm_mod.lm_init(key, cfg)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)

    h, _ = lm_mod.lm_hidden(params, cfg, tokens, ctx)
    full_logits = jnp.einsum(
        "bd,dv->bv", h[:, -1].astype(jnp.float32),
        (params["embed"]["embedding"].T if cfg.tie_embeddings
         else params["lm_head"]["kernel"]).astype(jnp.float32))

    _, cache = lm_mod.lm_prefill(params, cfg, tokens[:, :-1], ctx)
    cache = lm_mod.pad_cache(cache, 1)  # headroom for the decoded token
    step_logits, _ = lm_mod.lm_decode_step(params, cfg, tokens[:, -1], cache, ctx)

    scale = float(jnp.max(jnp.abs(full_logits))) + 1e-6
    err = float(jnp.max(jnp.abs(step_logits - full_logits))) / scale
    assert err < 0.05, f"{arch_id}: decode/teacher-forced mismatch rel={err}"


@pytest.mark.parametrize("arch_id", ["llama3.2-1b", "olmoe-1b-7b", "mamba2-780m"])
def test_quantized_mode_runs(arch_id):
    """The paper's numerics as a config switch: quant mode trains finitely."""
    arch = ARCHS[arch_id]
    cfg = arch.reduced()
    key = jax.random.PRNGKey(2)
    ctx = SpringContext(cfg=QUANT, keys=KeyGen(key))
    params = lm_mod.lm_init(key, cfg)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    loss, _ = lm_mod.lm_loss(params, cfg, tokens, ctx)
    assert bool(jnp.isfinite(loss))
    grads = jax.grad(lambda p: lm_mod.lm_loss(
        p, cfg, tokens, SpringContext(cfg=QUANT, keys=KeyGen(key)))[0])(params)
    assert _finite_tree(grads)


def test_whisper_decode_step():
    arch = ARCHS["whisper-medium"]
    cfg = arch.reduced()
    key = jax.random.PRNGKey(3)
    ctx = SpringContext()
    B = 2
    params = ed_mod.encdec_init(key, cfg)
    frames = jax.random.normal(key, (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    cache = ed_mod.encdec_init_cache(params, cfg, frames, ctx, max_len=8)
    tok = jnp.zeros((B,), jnp.int32)
    for _ in range(3):
        logits, cache = ed_mod.encdec_decode_step(params, cfg, tok, cache, ctx)
        tok = jnp.argmax(logits, -1)
    assert logits.shape == (B, cfg.vocab) and bool(jnp.all(jnp.isfinite(logits)))


def test_moe_capacity_and_balance_loss():
    from repro.models.moe import MoESpec, moe_apply, moe_init

    spec = MoESpec(n_experts=8, top_k=2, d_ff=32)
    key = jax.random.PRNGKey(0)
    params = moe_init(key, 16, spec)
    x = jax.random.normal(key, (2, 24, 16))
    y, aux, rows = moe_apply(params, x, SpringContext(), spec)
    assert y.shape == x.shape and bool(jnp.all(jnp.isfinite(y)))
    assert float(aux) >= 1.0 - 1e-3  # >= 1 by Cauchy-Schwarz at any routing
    # serving's capacity: 48 tokens x 2 slots over 8 experts, 15 rows each
    live, buffer, dropped = (float(v) for v in rows)
    assert buffer == 8 * 15 and live + dropped == 48 * 2
