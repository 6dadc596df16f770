"""Serving parity seal (ISSUE 4, satellite 1).

The continuous-batching engine must be *bit-identical*, per request, to
the pre-refactor static batch path — kept verbatim as
``launch.serve.static_reference_session`` — for a fixed (arch, seed,
mode) triple, across all three numerics modes; and a request's tokens
must be invariant to batch composition (slot count, co-tenants, queueing
order of strangers).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.serve import serve_session, serving_config, static_reference_session
from repro.optim.optimizers import OptimizerConfig
from repro.runtime.train import StepConfig
from repro.serving.engine import ServingEngine

pytestmark = pytest.mark.serving

ARCH = "llama3.2-1b"
BATCH, PROMPT, GEN = 3, 8, 5


def _tokens(out) -> np.ndarray:
    return np.asarray(out["generated"])


@pytest.mark.parametrize("mode", ["dense", "quant", "quant_sparse"])
def test_engine_matches_static_reference(mode):
    """Same arch/seed/mode: engine greedy tokens == static-path tokens,
    bit-identical, even when a 2-slot pool forces mid-flight joins."""
    static = static_reference_session(
        ARCH, reduced=True, batch=BATCH, prompt_len=PROMPT, gen=GEN, mode=mode)
    engine_full = serve_session(
        ARCH, reduced=True, batch=BATCH, prompt_len=PROMPT, gen=GEN, mode=mode)
    engine_tight = serve_session(
        ARCH, reduced=True, batch=BATCH, prompt_len=PROMPT, gen=GEN, mode=mode,
        slots=2)
    np.testing.assert_array_equal(_tokens(engine_full), _tokens(static))
    np.testing.assert_array_equal(_tokens(engine_tight), _tokens(static))
    assert engine_full["finite"] and engine_tight["finite"]


def _engine(step_cfg, params, cfg_view, n_slots, max_len=64):
    return ServingEngine(cfg_view, step_cfg, params=params, n_slots=n_slots,
                         max_len=max_len)


def _run_prompts(view, step_cfg, params, prompts, gen, n_slots, eos=None):
    eng = _engine(step_cfg, params, view, n_slots)
    for i, p in enumerate(prompts):
        eng.submit_prompt(p, gen, seed=100 + i, eos_id=eos)
    out = eng.run()
    return [r["tokens"] for r in out["per_request"]], out


@pytest.fixture(scope="module")
def small_model():
    arch = get_arch(ARCH)
    view = arch.view(reduced=True)
    step_cfg = StepConfig(spring=serving_config("quant_sparse"),
                          optimizer=OptimizerConfig())
    from repro.models.lm import lm_init

    params = lm_init(jax.random.PRNGKey(0), view.config)
    key = jax.random.PRNGKey(3)
    prompts = [
        [int(t) for t in jax.random.randint(jax.random.fold_in(key, i),
                                            (PROMPT + i,), 0, view.config.vocab)]
        for i in range(4)
    ]
    return view, step_cfg, params, prompts


def test_tokens_invariant_to_batch_composition(small_model):
    """A request's tokens don't change when strangers share its batch:
    alone vs 3 co-tenants vs different slot counts, ragged prompt lengths."""
    view, step_cfg, params, prompts = small_model
    alone, _ = _run_prompts(view, step_cfg, params, prompts[:1], GEN, n_slots=2)
    together, _ = _run_prompts(view, step_cfg, params, prompts, GEN, n_slots=4)
    queued, _ = _run_prompts(view, step_cfg, params, prompts, GEN, n_slots=2)
    assert together[0] == alone[0]
    assert queued == together
    # and under a different co-tenant ordering (request 0 admitted last)
    rev, out = _run_prompts(view, step_cfg, params,
                            prompts[1:] + prompts[:1], GEN, n_slots=2)
    assert rev[-1] == alone[0]
    assert out["finite"]


def test_eos_truncates_and_is_included(small_model):
    """A request retires on EOS with exactly min(steps-to-eos, max_tokens)
    tokens, EOS included; co-tenants are unaffected by its early exit."""
    view, step_cfg, params, prompts = small_model
    base, _ = _run_prompts(view, step_cfg, params, prompts[:2], GEN, n_slots=2)
    # EOS: the first token after step 1 that request 0 has not emitted
    # before, so the request stops there and not on an earlier repeat
    cut = next(k for k in range(1, len(base[0]))
               if base[0][k] not in base[0][:k])
    eos = base[0][cut]
    got, _ = _run_prompts(view, step_cfg, params, prompts[:2], GEN, n_slots=2,
                          eos=eos)
    assert got[0] == base[0][:cut + 1] and got[0][-1] == eos
    # request 1 may legitimately also hit this eos token; only check that
    # what it did emit is the unchanged prefix of its eos-free generation
    assert got[1] == base[1][: len(got[1])]


def test_serving_config_is_deterministic():
    """Serving numerics round to nearest: SR noise is drawn batch-wide,
    which would break batch-composition invariance (DESIGN.md §9)."""
    for mode in ("dense", "quant", "quant_sparse"):
        cfg = serving_config(mode)
        assert cfg.stochastic is False
        assert cfg.mode == mode


def test_one_shot_wrapper_surfaces_engine_metrics():
    out = serve_session(ARCH, reduced=True, batch=2, prompt_len=6, gen=3,
                        mode="quant_sparse", slots=2)
    assert out["engine"] is True
    assert out["generated"].shape == (2, 3)
    assert len(out["per_request"]) == 2
    for r in out["per_request"]:
        assert r["n_tokens"] == 3
        assert r["latency_s"] >= r["queue_s"] >= 0.0
    assert out["decode_steps"] >= 3
    assert 0.0 < out["mean_occupancy"] <= 1.0
    assert out["kv_mean_wire_bytes"] > 0.0
    assert out["kv_traffic_reduction_vs_fp32"] > 1.0


def test_engine_rejects_oversized_request():
    arch = get_arch(ARCH)
    view = arch.view(reduced=True)
    step_cfg = StepConfig(spring=serving_config("dense"),
                          optimizer=OptimizerConfig())
    eng = ServingEngine(view, step_cfg, n_slots=1, max_len=8)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit_prompt(list(range(6)), 4)


def test_sampled_decode_uses_per_request_keys(small_model):
    """Non-greedy decode is a function of the request's own seed: same
    request alone vs batched draws identical tokens."""
    view, step_cfg, params, prompts = small_model

    def run(plist, slots):
        eng = _engine(step_cfg, params, view, slots)
        eng.greedy = False
        for i, p in enumerate(plist):
            eng.submit_prompt(p, GEN, seed=41)  # seed fixed per submission order
        return [r["tokens"] for r in eng.run()["per_request"]]

    alone = run(prompts[:1], 2)
    batched = run(prompts[:1] + prompts[1:3], 3)
    assert batched[0] == alone[0]
