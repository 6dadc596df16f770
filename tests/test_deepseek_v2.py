"""DeepSeek-V2-Lite's mechanisms against the benchmark's plain float32
reference (``benchmarks/spring_bench/reference/deepseek-v2-lite.py``), on
the registry's reduced preset with seeded random weights: forward logits,
loss and first-step gradients in ``dense`` and ``quant_sparse``; the
expert share; dropless routing; the MoE row counter; prefill and decode
through the MLA cache."""

from __future__ import annotations

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.spring_ops import KeyGen, SpringConfig
from repro.models import lm as lm_mod
from repro.models.layers import SpringContext, swiglu_apply
from repro.models.moe import moe_apply

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "spring_bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
import harness  # noqa: E402

ref = harness.load_module(BENCH / "reference" / "deepseek-v2-lite.py")

ARCH = ARCHS["deepseek-v2-lite-16b"]
HELD = 4  # of the reduced preset's 8 routed experts

#: the reduced preset in the configuration file's keys (the published
#: rope_scaling, eps and gating; tiny widths), holding HELD experts
TINY = {
    "hidden_size": 64, "intermediate_size": 160, "kv_lora_rank": 32,
    "moe_intermediate_size": 48, "n_routed_experts": HELD, "n_shared_experts": 2,
    "norm_topk_prob": False, "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707},
    "routed_scaling_factor": 1, "vocab_size": 512, "aux_loss_alpha": 0.001,
    "published": {"n_routed_experts": 8},
}
F32 = SpringConfig(mode="dense", dense_dtype=jnp.float32)


def _cfg(held=HELD):
    return dataclasses.replace(ARCH.reduced(), experts_held=held)


def _tiny(held=HELD):
    return dict(TINY, n_routed_experts=held)


def _leaf_gaps(got, want) -> dict:
    """|got - want| / |want| (Frobenius) per leaf path."""
    return {jax.tree_util.keystr(p): float(jnp.linalg.norm(g - w) / (jnp.linalg.norm(w) + 1e-30))
            for (p, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                 jax.tree_util.tree_leaves(want))}


@functools.lru_cache(maxsize=None)
def _reference(seq: int = 32):
    """The reference's loss, gradients and logits at the tiny size."""
    params = ref.init_params(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, seq), 0, TINY["vocab_size"])
    b, count = tokens.shape[0], tokens.shape[0] * (seq - 1)

    @jax.jit
    def run(p):
        def loss(p):
            return sum(ref.row_loss(TINY, "f32", p, tokens[i], count, b) for i in range(b))
        value, grads = jax.value_and_grad(loss)(p)
        logits = jnp.stack([ref.logits(TINY, "f32", p, tokens[i]) for i in range(b)])
        return value, grads, logits

    return (params, tokens) + run(params)


def test_reference_lays_out_the_programs_parameters():
    """The reference makes the weights the program trains: the same tree,
    shapes and dtypes as ``lm_init`` for the same expert share."""
    for held in (HELD, 8):
        want = jax.eval_shape(lambda: lm_mod.lm_init(jax.random.PRNGKey(0), _cfg(held)))
        got = jax.eval_shape(lambda: ref.init_params(_tiny(held), jax.random.PRNGKey(0)))
        assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
        assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), got) == \
            jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)


#: (mode, loss, logits, gradient) tolerances.  dense in float32: the two
#: differ in summation order alone (seen: 0, 1.7e-6, 2.2e-6).  quant_sparse
#: rounds every operand and product onto Q4.16 (steps of 2^-16 = 1.5e-5)
#: stochastically, a relative noise of about 1e-4 on values of order 0.1
#: (seen: 1.3e-5, 6.1e-4, 6.2e-4); a lower precision (bf16, 8 bits)
#: would read about 1e-2.
TOLERANCES = [("dense", 1e-5, 2e-5, 2e-5), ("quant_sparse", 1e-4, 3e-3, 3e-3)]


@pytest.mark.parametrize("mode,tol_loss,tol_logits,tol_grad", TOLERANCES)
def test_program_matches_reference(mode, tol_loss, tol_logits, tol_grad):
    params, tokens, r_loss, r_grads, r_logits = _reference()
    cfg = _cfg()
    spring = F32 if mode == "dense" else SpringConfig(mode="quant_sparse")

    def ctx():
        return SpringContext(cfg=spring, keys=KeyGen(jax.random.PRNGKey(5))
                             if spring.is_quantized else None)

    @jax.jit
    def run(p):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm_mod.lm_loss(p, cfg, tokens, ctx()), has_aux=True)(p)
        h, _ = lm_mod.lm_hidden(p, cfg, tokens, ctx())
        return loss, metrics, grads, h.astype(jnp.float32) @ p["lm_head"]["kernel"]

    loss, metrics, grads, logits = run(params)
    assert abs(float(loss) - float(r_loss)) < tol_loss
    assert float(jnp.max(jnp.abs(logits - r_logits)) / jnp.max(jnp.abs(r_logits))) < tol_logits
    gaps = _leaf_gaps(grads, r_grads)
    assert max(gaps.values()) < tol_grad, max(gaps.items(), key=lambda kv: kv[1])
    assert float(metrics["moe_rows"][2]) == 0.0  # training drops nothing


def _moe_inputs(seed=0, t=24):
    params = ref.init_params(_tiny(8), jax.random.PRNGKey(seed))
    layer = jax.tree_util.tree_map(lambda x: x[0], params["unit_0"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, t, TINY["hidden_size"]))
    return layer, x


def _share(layer, s, held):
    """Chip s's share of the layer: its experts, and the router's columns
    rotated so that they come first (the layer holds experts [0, held))."""
    return dict(layer, router={"kernel": jnp.roll(layer["router"]["kernel"], -s * held, axis=1)},
                **{w: layer[w][s * held:(s + 1) * held] for w in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("held", [2, 4])
def test_expert_shares_add_up_to_the_whole_layer(held):
    """Every chip's routed share, plus the shared experts counted once,
    equals the uncut reference layer; each share's balance loss is the
    whole router's."""
    layer, x = _moe_inputs()
    spec = ARCH.reduced().moe
    routed_only = dataclasses.replace(spec, n_shared=0)
    ctx = SpringContext(cfg=F32)
    total = swiglu_apply(layer["shared"], x, ctx)
    for s in range(spec.n_experts // held):
        y, aux, rows = moe_apply(_share(layer, s, held), x, ctx, routed_only, dropless=True)
        total = total + y
    for b in range(x.shape[0]):
        want, balance = ref.moe(_tiny(8), "f32", x[b], layer)
        np.testing.assert_allclose(total[b], want, rtol=1e-5, atol=1e-5)
    _, want_aux = jax.vmap(lambda r: ref.moe(_tiny(8), "f32", r, layer))(x)
    np.testing.assert_allclose(aux, jnp.mean(want_aux), rtol=1e-5)


#: quant_sparse's stochastic Q4.16 rounding (steps of 2^-16) of the
#: expert inputs, weights and products, relative to the largest output
#: (seen: 6.7e-5); dense float32 differs in summation order alone
QS_MOE_TOL = 1e-3


def _qs_ctx():
    return SpringContext(cfg=SpringConfig(mode="quant_sparse"),
                         keys=KeyGen(jax.random.PRNGKey(9)))


def test_dropless_under_adversarial_routing():
    """Every token's top-k on held experts 0 and 1: training drops no
    pair and equals the reference, in dense (pairs packed for
    ``ragged_dot``) and in quant_sparse (a T-row buffer per held expert);
    serving's capacity drops some."""
    layer, x = _moe_inputs(seed=2)
    # within Q4.16's range (|v| < 8), so quant_sparse only rounds
    x = x.at[..., 0].set(4.0)
    router = layer["router"]["kernel"].at[0].set(-12.5).at[0, :2].set(12.5)
    layer = _share(dict(layer, router={"kernel": router}), 0, HELD)
    spec = dataclasses.replace(ARCH.reduced().moe, capacity_factor=1.25)
    ctx = SpringContext(cfg=F32)
    t, k = x.shape[0] * x.shape[1], spec.top_k
    want = jnp.stack([ref.moe(_tiny(), "f32", x[b], layer)[0] for b in range(x.shape[0])])
    y, _, rows = moe_apply(layer, x, ctx, spec, dropless=True)
    assert rows.tolist() == [t * k, t * k, 0.0]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    y, _, rows = moe_apply(layer, x, _qs_ctx(), spec, dropless=True)
    assert rows.tolist() == [t * k, HELD * t, 0.0]
    assert float(jnp.max(jnp.abs(y - want)) / jnp.max(jnp.abs(want))) < QS_MOE_TOL
    _, _, served = moe_apply(layer, x, ctx, spec)
    assert served[2] > 0 and served[0] + served[2] == t * k


def _numpy_rows(layer, x, spec, held, cap, buffer):
    """[live, buffer, dropped] counted in numpy: float64 routing, each held
    pair's place among its expert's pairs in token-major order."""
    flat = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = flat @ np.asarray(layer["router"]["kernel"], np.float64)
    top = np.argsort(-logits, axis=1, kind="stable")[:, :spec.top_k]
    seen = np.zeros(held, int)
    live = dropped = 0
    for e in top.reshape(-1):
        if e < held:
            live, dropped = (live + 1, dropped) if seen[e] < cap else (live, dropped + 1)
            seen[e] += 1
    return [live, buffer, dropped]


@pytest.mark.parametrize("dropless", [True, False])
def test_moe_rows_equal_a_numpy_count(dropless):
    """Dropless training's buffer is T * k packed rows in dense and T rows
    a held expert in quant_sparse; serving's is its capacity a held expert."""
    layer, x = _moe_inputs(seed=4, t=40)
    held = 3
    layer = _share(layer, 0, held)
    spec = dataclasses.replace(ARCH.reduced().moe, capacity_factor=1.0)
    t, k = x.shape[0] * x.shape[1], spec.top_k
    cap = t if dropless else int(t * k / spec.n_experts * spec.capacity_factor + 0.999)
    _, _, rows = moe_apply(layer, x, SpringContext(cfg=F32), spec, dropless=dropless)
    want = _numpy_rows(layer, x, spec, held, cap, t * k if dropless else held * cap)
    assert rows.tolist() == want
    assert (want[2] == 0) == dropless
    if dropless:
        _, _, rows = moe_apply(layer, x, _qs_ctx(), spec, dropless=True)
        assert rows.tolist()[1:] == [held * t, 0.0]


def test_prefill_and_decode_match_the_teacher_forced_forward():
    """Prefill of 12 tokens then 6 decode steps through the MLA cache (the
    normed latent, YaRN rope at each position, mscale^2 in the absorbed
    scores) give the logits of the full forward at every position.  The
    cache holds bf16, which sets the tolerance."""
    cfg = _cfg()
    params = ref.init_params(TINY, jax.random.PRNGKey(6))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 18), 0, TINY["vocab_size"])
    ctx = SpringContext(cfg=F32)
    h, _ = lm_mod.lm_hidden(params, cfg, tokens, ctx)
    full = h @ params["lm_head"]["kernel"]
    logits, cache = lm_mod.lm_prefill(params, cfg, tokens[:, :12], ctx)
    cache = lm_mod.pad_cache(cache, 6)
    scale = float(jnp.max(jnp.abs(full)))
    for i in range(12, 18):
        err = float(jnp.max(jnp.abs(logits - full[:, i - 1]))) / scale
        assert err < 1e-2, (i, err)
        logits, cache = lm_mod.lm_decode_step(params, cfg, tokens[:, i], cache, ctx)
    # the published scale: 192^-0.5 * mscale(40, 0.707)^2 at full width
    from repro.models.attention import mla_softmax_scale

    assert mla_softmax_scale(ARCH.config.mla) == pytest.approx(0.11472, abs=5e-6)
    assert mla_softmax_scale(ARCH.config.mla) == pytest.approx(ref.softmax_scale(
        {"qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rope_scaling": TINY["rope_scaling"]}))


def test_yarn_rope_is_the_published_embedding():
    """The program's rope on interleaved pairs with YaRN equals the
    published de-interleave-then-rotate_half form with its cos/sin tables;
    the ramp runs between correction dims 10 and 23 of 32 at full width."""
    from repro.models.layers import YarnSpec, rope_apply, yarn_ramp

    y = TINY["rope_scaling"]
    yarn = YarnSpec(factor=40, original_max_position=4096, beta_fast=32, beta_slow=1,
                    mscale=0.707, mscale_all_dim=0.707)
    ramp = yarn_ramp(64, 10000.0, yarn)
    assert ramp[10] == 0.0 and 0.0 < ramp[11] < 1.0 and ramp[23] == 1.0 and ramp[22] < 1.0
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 300, 2, 64))
    pos = jnp.arange(300)[None]
    got = rope_apply(x, pos, 10000.0, yarn=yarn, interleaved=True)
    cos, sin = ref.rope_tables({"rope_scaling": y, "qk_rope_head_dim": 64, "rope_theta": 10000}, 300)
    want = ref.apply_rope(jnp.swapaxes(x[0], 0, 1), cos, sin)
    np.testing.assert_allclose(jnp.swapaxes(got[0], 0, 1), want, rtol=1e-5, atol=1e-5)
