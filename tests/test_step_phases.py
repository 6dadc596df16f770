"""The train step's own measurement, inside the jitted program: the tile
counter ``metrics["mm_tiles"]`` against tile counts taken eagerly from
the operands of every masked_matmul call, under the layer scan and both
recomputing policies; and the named device phases in the step's
optimized HLO."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.masked_matmul import ops as mm_ops

SCOPES = ("spring_quantize", "spring_mm_prep", "spring_ssd_scan_vjp", "spring_optimizer")


def _step(mode="quant_sparse", remat_policy="full", sets=()):
    """A jitted train step of the reduced mamba2 (4 scanned layers, remat
    on), its state with in_proj's columns 128:256 zeroed (one empty
    weight tile per forward and dx call) and one batch."""
    from repro.api.spec import build_spec
    from repro.models.lm import lm_init
    from repro.optim.optimizers import make_optimizer
    from repro.runtime.train import TrainState, make_train_step

    r = build_spec("train", use_env=False, sets=[
        "arch.id=mamba2-780m", "arch.reduced=true", "shape.batch=2",
        "shape.seq=64", f"numerics.mode={mode}", "sparsity.backward=auto",
        *sets]).resolve()
    cfg = dataclasses.replace(r.config, remat=True, remat_policy=remat_policy)
    assert cfg.n_units == 4
    params = lm_init(jax.random.PRNGKey(0), cfg)
    mixer = params["unit_0"]["mixer"]
    mixer["in_proj"]["kernel"] = mixer["in_proj"]["kernel"].at[:, :, 128:256].set(0.0)
    opt_init, _ = make_optimizer(r.step.optimizer)
    state = TrainState(params, opt_init(params), jnp.zeros((), jnp.int32),
                       jax.random.PRNGKey(1), None)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, cfg.vocab)}
    return jax.jit(make_train_step(r.arch.view(config=cfg), r.step)), state, batch


def _block(tiles: int) -> int:
    """Tiles per block side: the largest side of 512, 384, 256, 128 that
    divides the padded dim, in tiles."""
    return next(r for r in (4, 3, 2, 1) if tiles % r == 0)


def _counts(a, b) -> list:
    """[issued, total] 128x128 tile steps of ``a @ b``, padding included,
    and [one_dot, total, idle] block grid steps (one_dot: blocks whose
    tiles are all occupied in both operands; idle: blocks where no tile
    is occupied in both), in numpy."""
    a, b = np.asarray(a), np.asarray(b)
    (m, k), n = a.shape, b.shape[1]
    mi, ki, ni = -(-m // 128), -(-k // 128), -(-n // 128)
    ap = np.zeros((mi * 128, ki * 128))
    ap[:m, :k] = a
    bp = np.zeros((ki * 128, ni * 128))
    bp[:k, :n] = b
    a_occ = (ap.reshape(mi, 128, ki, 128) != 0).any(axis=(1, 3))
    b_occ = (bp.reshape(ki, 128, ni, 128) != 0).any(axis=(1, 3))
    rm, rk, rn = _block(mi), _block(ki), _block(ni)
    a_full = a_occ.reshape(mi // rm, rm, ki // rk, rk).all(axis=(1, 3))
    b_full = b_occ.reshape(ki // rk, rk, ni // rn, rn).all(axis=(1, 3))
    pairs = a_occ[:, :, None] & b_occ[None]                   # (mi, ki, ni)
    busy = pairs.reshape(mi // rm, rm, ki // rk, rk, ni // rn, rn).any(axis=(1, 3, 5))
    return [int(pairs.sum()), mi * ki * ni,
            int((a_full[:, :, None] & b_full[None]).sum()), a_full.size * b_full.shape[1],
            int((~busy).sum())]


def _probe(x, w, g) -> list:
    """The probe's 9 counts of one call: each direction's tile counts,
    then the block counts summed over the three."""
    fwd, dx, dw = _counts(x, w), _counts(g, w.T), _counts(x.T, g)
    return fwd[:2] + dx[:2] + dw[:2] + [fwd[i] + dx[i] + dw[i] for i in (2, 3, 4)]


@pytest.mark.parametrize("remat_policy", ["full", "stash"])
def test_tile_counter_equals_eager_counts(monkeypatch, remat_policy):
    """Each call's forward (x @ w), dx (g @ w.T) and dw (x.T @ g) tile
    and block counts (idle blocks among them), taken from its operands by a host callback in the
    backward rule, sum to the step's in-jit counter exactly: the layers
    recomputed by ``jax.checkpoint`` or restored from the memstash
    compressed stash."""
    seen = []
    real = mm_ops._mm.bwd

    def bwd(il, fl, apply_sr, lowerings, res, g):
        x, w = res[0], res[1]
        jax.debug.callback(lambda x, w, g: seen.append(_probe(x, w, g)), x, w, g)
        return real(il, fl, apply_sr, lowerings, res, g)

    monkeypatch.setattr(mm_ops._mm, "bwd", bwd)
    sets = ["memstash.policy=stash"] if remat_policy == "stash" else []
    step, state, batch = _step(remat_policy=remat_policy, sets=sets)
    _, metrics = step(state, batch)
    tiles = np.asarray(metrics["mm_tiles"])
    jax.effects_barrier()
    assert len(seen) == 4 * 2  # in_proj and out_proj of 4 scanned layers
    want = np.sum(seen, axis=0)
    assert tiles.dtype == np.float32 and tiles.tolist() == want.tolist()
    assert len(want) == mm_ops.PROBE_SIZE == 9
    # the zeroed weight tile: skipped tiles, blocks off the one-dot path,
    # and idle blocks whose copies the kernel elides
    assert want[0] < want[1] and want[2] < want[3] and want[6] < want[7]
    assert 0 < want[8] < want[7]


def test_dense_step_counts_nothing():
    step, state, batch = _step(mode="dense")
    _, metrics = step(state, batch)
    assert "mm_tiles" not in metrics


def test_backward_none_step_under_kernel_lowering():
    """``sparsity.backward=none`` trains with the kernel lowering pinned:
    the plain float32 product and autodiff, nothing counted."""
    step, state, batch = _step(sets=["sparsity.backward=none",
                                     "kernels.policy=masked_matmul=interpret"])
    _, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])) and "mm_tiles" not in metrics


def test_step_names_its_device_phases():
    """Each phase scope reaches the compiled step's instruction metadata
    (ssd_scan pinned to its kernel so that its backward is the VJP)."""
    step, state, batch = _step(sets=["kernels.policy=ssd_scan=interpret"])
    hlo = step.lower(state, batch).compile().as_text()
    for scope in SCOPES:
        assert f"/{scope}/" in hlo, scope


LAYER_SCOPES = ("spring_moe_dispatch", "spring_moe_combine", "spring_mla_attention")


@pytest.mark.parametrize("mode,microbatch", [
    pytest.param("dense", None, id="dense"),
    pytest.param("quant_sparse", None, id="quant_sparse"),
    pytest.param("quant_sparse", 2, id="quant_sparse-microbatch"),
])
def test_moe_and_mla_phases_and_row_counter(mode, microbatch):
    """DeepSeek-V2-Lite's reduced preset (MLA everywhere, a dense first
    layer, 2 MoE layers holding 4 of 8 experts): the layer scopes reach
    the compiled step, and ``moe_rows`` is counted in every mode, with
    no pair dropped, and summed over microbatches."""
    from repro.api.spec import build_spec
    from repro.models.lm import lm_init
    from repro.optim.optimizers import make_optimizer
    from repro.runtime.train import TrainState, make_train_step

    r = build_spec("train", use_env=False, sets=[
        "arch.id=deepseek-v2-lite-16b", "arch.reduced=true", "shape.batch=2",
        "shape.seq=32", f"numerics.mode={mode}", "sparsity.backward=auto"]
        + ([f"shape.microbatch={microbatch}"] if microbatch else [])).resolve()
    assert r.step.microbatch == microbatch
    cfg = dataclasses.replace(r.config, experts_held=4)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    opt_init, _ = make_optimizer(r.step.optimizer)
    state = TrainState(params, opt_init(params), jnp.zeros((), jnp.int32),
                       jax.random.PRNGKey(1), None)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, cfg.vocab)}
    step = jax.jit(make_train_step(r.arch.view(config=cfg), r.step))
    compiled = step.lower(state, batch).compile()
    hlo = compiled.as_text()
    for scope in LAYER_SCOPES:
        assert f"/{scope}/" in hlo, scope
    _, metrics = compiled(state, batch)
    live, buffer, dropped = np.asarray(metrics["moe_rows"]).tolist()
    # 2 layers of 64 tokens, 2 of 8 experts per token: quant_sparse holds 4
    # experts x 64 token rows a layer (microbatches: 2 x 4 x 32), dense packs
    # the held pairs into 64 x 2 rows
    want_buffer = 2 * (4 * 64 if mode == "quant_sparse" else 64 * 2)
    assert buffer == want_buffer and dropped == 0 and 0 < live <= 2 * 64 * 2
    assert ("mm_tiles" in metrics) == (mode == "quant_sparse")
