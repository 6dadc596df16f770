"""Sparsity-aware backward pass: gradient parity of the custom_vjp masked
kernels against the dense ref gradient, for every registered
``masked_matmul_dx`` / ``masked_matmul_dw`` implementation runnable on
this backend (pinned through the kernel policy), ``spring_matmul``
through the one custom_vjp against numpy, the spec threading and the
end-to-end stash/masked conv+fc acceptance check."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spring_ops import (
    BACKWARD_SPARSITY_CHOICES,
    QUANT_SPARSE,
    KeyGen,
    SpringConfig,
    spring_conv2d,
    spring_matmul,
)
from repro.kernels import registry
from repro.kernels.masked_matmul.backward import sparsity_probe
from repro.kernels.masked_matmul.ops import masked_matmul
from repro.kernels.masked_matmul.ref import masked_matmul_reference
from repro.kernels.registry import KernelPolicy

# every backward impl runnable on this backend (pallas is TPU-only)
BWD_IMPLS = sorted(
    name for name, k in registry.impls("masked_matmul_dx").items()
    if k.available()
)

DENSITIES = [0.0, 0.1, 0.5, 1.0]
# (M, K, N): square, non-square, tile-unaligned
SHAPES = [(128, 128, 128), (100, 70, 50), (64, 200, 96)]
FORMATS = [(4, 16), (2, 6)]  # fp32-grid Q4.16 and a reduced-precision grid


def _pinned(impl: str):
    """The kernel policy that pins both backward lowerings to ``impl``."""
    return registry.kernel_policy(masked_matmul_dx=impl, masked_matmul_dw=impl)


def _sparse(seed: int, shape, density: float) -> jax.Array:
    key = jax.random.PRNGKey(seed)
    v = jax.random.normal(key, shape) * 0.1
    keep = jax.random.uniform(jax.random.fold_in(key, 1), shape) < density
    return v * keep


# ---------------------------------------------------------------------------
# Registry completeness: an unregistered backward impl must fail here.
# ---------------------------------------------------------------------------


@pytest.mark.grad_parity
def test_backward_ops_registered_with_full_impl_ladder():
    """Every forward masked_matmul impl has a same-named dx and dw impl,
    and both backward ops carry parity examples so the registry-generated
    harness (tests/test_kernel_registry.py, bench --smoke) covers them."""
    fwd = set(registry.impls("masked_matmul"))
    # every forward backend has a matching backward impl, and no other
    assert fwd == set(registry.impls("masked_matmul_dx"))
    assert fwd == set(registry.impls("masked_matmul_dw"))
    assert registry.op_spec("masked_matmul_dx").examples is not None
    assert registry.op_spec("masked_matmul_dw").examples is not None
    # and they show up in the generated parity sweep on this backend
    pairs = set(registry.parity_pairs())
    for op in ("masked_matmul_dx", "masked_matmul_dw"):
        for name, k in registry.impls(op).items():
            if name != "ref" and k.available() and k.parity:
                assert (op, name) in pairs, f"({op}, {name}) not parity-swept"


# ---------------------------------------------------------------------------
# Gradient parity: custom_vjp path vs jax.grad of the pure dense path.
# ---------------------------------------------------------------------------


@pytest.mark.grad_parity
@pytest.mark.parametrize("impl", BWD_IMPLS)
@pytest.mark.parametrize("density", DENSITIES)
def test_grad_parity_all_shapes_and_formats(impl, density):
    """jax.grad through masked_matmul with dx/dw pinned to ``impl`` ==
    jax.grad through the dense ref matmul, across shapes and Q(il,fl)
    formats.  The ReLU in the loss makes the cotangent mask-structured
    (Sarma et al.)."""
    for m, k, n in SHAPES:
        for il, fl in FORMATS:
            x = _sparse(m * 31 + k, (m, k), density)
            w = _sparse(n * 17 + k, (k, n), density if density else 0.5)

            def loss_vjp(x, w):
                y = masked_matmul(x, w, il=il, fl=fl, apply_sr=False,
                                  impl="ref")
                return jnp.sum(jax.nn.relu(y) ** 2)

            def loss_dense(x, w):
                return jnp.sum(jax.nn.relu(
                    jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))) ** 2)

            with _pinned(impl):
                gx, gw = jax.grad(loss_vjp, argnums=(0, 1))(x, w)
            rx, rw = jax.grad(loss_dense, argnums=(0, 1))(x, w)
            np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.grad_parity
@pytest.mark.parametrize("impl", BWD_IMPLS)
def test_grad_parity_under_jit_and_auto(impl):
    x = _sparse(0, (96, 64), 0.5)
    w = _sparse(1, (64, 80), 0.5)

    def loss(x, w):
        y = masked_matmul(x, w, apply_sr=False, impl="ref")
        return jnp.sum(y ** 2)

    ref = jax.grad(lambda x, w: jnp.sum(jnp.dot(x, w) ** 2),
                   argnums=(0, 1))(x, w)
    for policy in (_pinned(impl), registry.kernel_policy()):
        with policy:
            got = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.grad_parity
def test_backward_dispatch_counts_attribute_impl():
    """The dx/dw resolutions show up in dispatch_counts under the pinned
    impl — backward backend choices are attributable, like forward ones."""
    x, w = _sparse(2, (64, 64), 0.5), _sparse(3, (64, 64), 0.5)
    registry.reset_dispatch_counts()
    with _pinned("interpret"):
        jax.grad(lambda x: jnp.sum(masked_matmul(
            x, w, apply_sr=False, impl="ref") ** 2))(x)
    counts = registry.dispatch_counts()
    assert counts["masked_matmul_dx"] == {"interpret": 1}
    assert counts["masked_matmul_dw"] == {"interpret": 1}


@pytest.mark.grad_parity
def test_forward_only_calls_count_no_backward_dispatch():
    """dx/dw are resolved at the call but count a dispatch only where they
    run: forward-only work (serving, eager inference) records none."""
    x, w = _sparse(2, (64, 64), 0.5), _sparse(3, (64, 64), 0.5)
    registry.reset_dispatch_counts()
    with _pinned("interpret"):
        masked_matmul(x, w, apply_sr=False, impl="ref")
        jax.jit(lambda x: masked_matmul(x, w, apply_sr=False, impl="ref"))(x)
    counts = registry.dispatch_counts()
    assert counts["masked_matmul"] == {"ref": 2}
    assert "masked_matmul_dx" not in counts and "masked_matmul_dw" not in counts


# ---------------------------------------------------------------------------
# spring_matmul / spring_conv2d routing under SpringConfig.backward_sparsity.
# ---------------------------------------------------------------------------


def _config_pinning(impl: str) -> KernelPolicy:
    """A config's kernel policy pinning dx and dw to ``impl`` ("auto": none)."""
    if impl == "auto":
        return KernelPolicy()
    return KernelPolicy(overrides=(("masked_matmul_dx", impl), ("masked_matmul_dw", impl)))


def _cfgs(impl: str):
    """quant_sparse with the sparse backward, dx/dw on ``impl``, and without it."""
    on = dataclasses.replace(QUANT_SPARSE, kernels=_config_pinning(impl))
    off = dataclasses.replace(QUANT_SPARSE, backward_sparsity="none")
    return on, off


@pytest.mark.grad_parity
@pytest.mark.parametrize("impl", BWD_IMPLS)
def test_spring_matmul_backward_matches_dense_autodiff(impl):
    """Forward numerics are bit-identical between the sparse backward on
    ``impl`` and "none" (both lower to the dense fp32 matmul + STE
    epilogue on CPU), and the sparsity-aware gradient is allclose to
    autodiff."""
    on, off = _cfgs(impl)
    x = jax.nn.relu(_sparse(6, (64, 48), 0.5) * 10)
    w = _sparse(7, (48, 32), 1.0)

    def loss(cfg):
        def f(x, w):
            y = spring_matmul(x, w, cfg, KeyGen(jax.random.PRNGKey(11)))
            return jnp.sum(jax.nn.relu(y) ** 2)
        return f

    y_on = spring_matmul(x, w, on, KeyGen(jax.random.PRNGKey(11)))
    y_off = spring_matmul(x, w, off, KeyGen(jax.random.PRNGKey(11)))
    np.testing.assert_array_equal(np.asarray(y_on), np.asarray(y_off))

    g_on = jax.grad(loss(on), argnums=(0, 1))(x, w)
    g_off = jax.grad(loss(off), argnums=(0, 1))(x, w)
    for a, b in zip(g_on, g_off):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.grad_parity
def test_spring_conv2d_backward_matches_dense_autodiff():
    """Both conv backward GEMMs (dX via dilated cotangent patches, dW via
    im2col of the stashed activation) match the dense conv VJP."""
    on, off = _cfgs("interpret")
    x = jax.nn.relu(_sparse(8, (2, 12, 12, 8), 0.5) * 10)
    w = _sparse(9, (3, 3, 8, 16), 1.0)

    for stride, padding in [((1, 1), "SAME"), ((2, 2), "SAME"), ((1, 1), "VALID")]:
        def loss(cfg):
            def f(x, w):
                y = spring_conv2d(x, w, cfg, KeyGen(jax.random.PRNGKey(13)),
                                  stride=stride, padding=padding)
                return jnp.sum(jax.nn.relu(y) ** 2)
            return f

        g_on = jax.grad(loss(on), argnums=(0, 1))(x, w)
        g_off = jax.grad(loss(off), argnums=(0, 1))(x, w)
        for a, b in zip(g_on, g_off):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5,
                atol=1e-5 * float(np.max(np.abs(np.asarray(b))) + 1.0))


@pytest.mark.grad_parity
def test_grouped_conv_falls_back_to_dense_autodiff():
    """Depthwise convs keep the dense VJP (patch matrices interleave
    groups) — gradients must still flow and match."""
    on, off = _cfgs("auto")
    x = jax.nn.relu(_sparse(10, (2, 8, 8, 8), 0.5) * 10)
    w = _sparse(11, (3, 3, 1, 8), 1.0)

    def loss(cfg):
        def f(x, w):
            y = spring_conv2d(x, w, cfg, KeyGen(jax.random.PRNGKey(17)),
                              feature_group_count=8)
            return jnp.sum(y ** 2)
        return f

    g_on = jax.grad(loss(on), argnums=(0, 1))(x, w)
    g_off = jax.grad(loss(off), argnums=(0, 1))(x, w)
    for a, b in zip(g_on, g_off):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_spring_config_validates_backward_sparsity():
    for name in BACKWARD_SPARSITY_CHOICES:
        SpringConfig(backward_sparsity=name)
    assert BACKWARD_SPARSITY_CHOICES == ("none", "auto")
    for name in ("cuda", "interpret", "jnp"):  # lowerings are pinned in kernels
        with pytest.raises(ValueError, match="backward_sparsity"):
            SpringConfig(backward_sparsity=name)


# ---------------------------------------------------------------------------
# spring_matmul through the one custom_vjp, at the kernel's idle-block
# shapes, against numpy: forward, dx, dw and the tile counter.
# ---------------------------------------------------------------------------


def _grid(seed: int, shape, fl: int = 8) -> jax.Array:
    """Q4.16 grid values (multiples of 2^-fl), so operand SR is an identity."""
    return jnp.round(jax.random.normal(jax.random.PRNGKey(seed), shape) * 2**(fl - 2)) / 2**fl


def _idle_cases():
    """(x, w, cotangent): an expert buffer's 200 leading live rows of 1024
    (its cotangent masked alike), every block working, x all empty."""
    live = _grid(20, (1024, 256)).at[200:].set(0.0)
    return {
        "leading_live_rows": (live, _grid(21, (256, 384)),
                              _grid(22, (1024, 384)).at[200:].set(0.0)),
        "every_block_works": (_grid(23, (512, 384)) + 2.0**-9, _grid(24, (384, 512)),
                              _grid(25, (512, 512))),
        "all_empty": (jnp.zeros((1024, 256)), _grid(26, (256, 384)),
                      _grid(27, (1024, 384))),
    }


@pytest.mark.grad_parity
@pytest.mark.parametrize("impl", ["interpret", "ref"])
@pytest.mark.parametrize("case", list(_idle_cases()))
def test_spring_matmul_one_custom_vjp_matches_numpy(case, impl):
    """Forward, dL/dx, dL/dw and the probe's nine tile and block counts
    of one quant_sparse ``spring_matmul``, every lowering pinned to
    ``impl`` on the config, against numpy in float64."""
    from test_step_phases import _probe

    x, w, c = _idle_cases()[case]
    kernels = KernelPolicy(overrides=tuple(
        (op, impl) for op in ("masked_matmul", "masked_matmul_dx", "masked_matmul_dw")))
    cfg = dataclasses.replace(QUANT_SPARSE, kernels=kernels)

    def loss(x, w, probe):
        y = spring_matmul(x, w, cfg, KeyGen(jax.random.PRNGKey(3)), probe=probe)
        return jnp.vdot(y, c), y

    (_, y), (dx, dw, tiles) = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        x, w, jnp.zeros((9,), jnp.float32))
    xn, wn, cn = (np.asarray(a, np.float64) for a in (x, w, c))
    np.testing.assert_allclose(np.asarray(y), xn @ wn, rtol=0, atol=2.0**-16)
    np.testing.assert_allclose(np.asarray(dx), cn @ wn.T, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), xn.T @ cn, rtol=1e-5, atol=1e-6)
    assert np.asarray(tiles).tolist() == _probe(x, w, c)


@pytest.mark.grad_parity
@pytest.mark.parametrize("impl", ["interpret", "ref"])
def test_kernel_lowering_sr_ignores_the_key(impl):
    """ROADMAP D10, pinned as the program stands: a kernel lowering fuses
    the SR epilogue with seed 0, so ``spring_matmul``'s output on grid
    operands is the same whatever the key; the ref lowering rounds with
    the key.  Fixing D10 changes this test on purpose."""
    # multiples of 2^-12: the products fall between Q4.16's grid points
    x, w = _grid(30, (256, 384), fl=12), _grid(31, (384, 128), fl=12)
    cfg = dataclasses.replace(QUANT_SPARSE, kernels=KernelPolicy.parse(f"masked_matmul={impl}"))
    a, b = (np.asarray(spring_matmul(x, w, cfg, KeyGen(jax.random.PRNGKey(k))))
            for k in (1, 2))
    if impl == "interpret":
        assert (a == b).all()
        assert (a == np.asarray(masked_matmul_reference(x, w, jnp.uint32(0)))).all()
    else:
        assert (a != b).sum() > a.size // 10


@pytest.mark.grad_parity
@pytest.mark.parametrize("layer", ["matmul", "conv"])
def test_config_policy_pins_backward_lowerings(layer):
    """A dx/dw pin set on ``SpringConfig.kernels`` alone, with no ambient
    policy, picks the backward lowerings of both layer kinds."""
    cfg = dataclasses.replace(QUANT_SPARSE, kernels=_config_pinning("interpret"))
    if layer == "matmul":
        x, w = _grid(32, (128, 256)), _grid(33, (256, 128))
        f = lambda x: jnp.sum(spring_matmul(x, w, cfg, KeyGen(jax.random.PRNGKey(4))) ** 2)
    else:
        x, w = _grid(34, (2, 8, 8, 8)), _grid(35, (3, 3, 8, 8))
        f = lambda x: jnp.sum(spring_conv2d(x, w, cfg, KeyGen(jax.random.PRNGKey(4))) ** 2)
    assert registry.current_policy().is_auto
    registry.reset_dispatch_counts()
    jax.grad(f)(x)
    counts = registry.dispatch_counts()
    assert counts["masked_matmul_dx"] == {"interpret": 1}
    assert counts["masked_matmul_dw"] == {"interpret": 1}
    assert counts.get("masked_matmul") == ({"ref": 1} if layer == "matmul" else None)


@pytest.mark.grad_parity
def test_backward_none_trains_under_the_kernel_lowering():
    """``backward_sparsity="none"`` with the kernel lowering pinned takes
    the plain float32 product and JAX autodiff, bit for bit as with the
    ref lowering (a forward-only kernel call used to raise here)."""
    x, w = jax.nn.relu(_grid(36, (128, 256))), _grid(37, (256, 128))
    plain = dataclasses.replace(QUANT_SPARSE, backward_sparsity="none")
    kernel = dataclasses.replace(plain, kernels=KernelPolicy.parse("masked_matmul=interpret"))

    def run(cfg):
        def f(x, w):
            y = spring_matmul(x, w, cfg, KeyGen(jax.random.PRNGKey(5)))
            return jnp.sum(jax.nn.relu(y) ** 2), y
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(x, w)

    for a, b in zip(jax.tree_util.tree_leaves(run(kernel)),
                    jax.tree_util.tree_leaves(run(plain))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# End-to-end acceptance: stash/masked conv+fc model, the backward pinned
# to the tile-skipping kernel, vs the dense ref gradient.
# ---------------------------------------------------------------------------


@pytest.mark.grad_parity
def test_conv_fc_model_grad_parity_with_stash():
    """jax.grad through a stash/masked conv+fc model with the backward
    pinned to "interpret" (the CPU stand-in for "pallas") is
    allclose (rtol 1e-5) to the dense ref gradient, with the memstash
    compressed-activation stash active at every conv/fc point."""
    from repro.memstash.config import MemstashConfig
    from repro.models.cnn import ParamStore, conv, fc
    from repro.models.layers import SpringContext

    def model(store, ctx, x):
        h = conv(store, ctx, "c1", x, 8, k=3)
        h = conv(store, ctx, "c2", h, 8, k=3, stride=2)
        h = h.reshape(h.shape[0], -1)
        h = fc(store, ctx, "f1", h, 32, relu=True)
        return fc(store, ctx, "f2", h, 10)

    key = jax.random.PRNGKey(0)
    x = jax.nn.relu(jax.random.normal(key, (2, 8, 8, 3)))
    init_store = ParamStore(jax.random.fold_in(key, 1))
    model(init_store, SpringContext(), x)
    params = init_store.params

    def loss(params, cfg):
        ctx = SpringContext(cfg=cfg, keys=KeyGen(jax.random.PRNGKey(2)),
                            memstash=MemstashConfig(policy="stash"))
        y = model(ParamStore(key, params), ctx, x)
        return jnp.mean(y ** 2)

    on, off = _cfgs("interpret")
    g_sparse = jax.grad(lambda p: loss(p, on))(params)
    g_ref = jax.grad(lambda p: loss(p, off))(params)
    for name in params:
        np.testing.assert_allclose(
            np.asarray(g_sparse[name]), np.asarray(g_ref[name]),
            rtol=1e-5, atol=1e-6, err_msg=name)


@pytest.mark.grad_parity
def test_step_config_threads_backward_sparsity_into_train_step():
    """``sparsity.backward`` lands on the SpringConfig the train step
    builds its contexts from; a backward lowering is pinned only through
    ``kernels.policy``."""
    from repro.api.spec import SpecError, build_spec

    def resolved(*sets):
        return build_spec("train", use_env=False, sets=[
            "numerics.mode=quant_sparse", *sets]).resolve()

    r = resolved()
    assert r.spring.backward_sparsity == "auto" and r.spring.sparse_backward
    assert r.step.spring is r.spring
    off = resolved("sparsity.backward=none")
    assert off.step.spring.backward_sparsity == "none"
    assert not off.step.spring.sparse_backward
    with pytest.raises(SpecError, match="sparsity.backward"):
        resolved("sparsity.backward=interpret")
    pinned = resolved("kernels.policy=masked_matmul_dx=interpret")
    assert pinned.step.spring.kernels.impl_for("masked_matmul_dx") == "interpret"


@pytest.mark.grad_parity
def test_sparsity_probe_reports_nonzero_backward_skip():
    """The dry-run's eager probe: at 50% tile-granular density the
    backward GEMMs skip a nonzero fraction of MXU grid steps (the
    acceptance criterion's dryrun JSON field)."""
    p = sparsity_probe(density=0.5, size=256)
    assert p["forward_tile_skip"] is not None and p["forward_tile_skip"] > 0.0
    assert p["backward_tile_skip"] is not None and p["backward_tile_skip"] > 0.0
    assert p["backward_tile_skip_dx"] > 0.0
    assert p["backward_tile_skip_dw"] > 0.0
    # denser operands skip less
    p_dense = sparsity_probe(density=1.0, size=256)
    assert p_dense["backward_tile_skip"] <= p["backward_tile_skip"]


@pytest.mark.grad_parity
def test_measured_backward_skip_feeds_perfmodel():
    """measured_backward_skip_fraction -> spring_eval: the training-time
    compute term scales as fwd + 2x bwd with independent skip fractions."""
    from repro.models.cnn import LayerRecord
    from repro.perfmodel.spring_model import (
        measured_backward_skip_fraction,
        spring_eval,
    )

    x = jnp.zeros((256, 256)).at[:128, :128].set(1.0)
    w = jnp.ones((256, 256))
    with registry.record_kernel_metrics():
        pass
    with registry.record_kernel_metrics() as rows:
        jax.grad(lambda x: jnp.sum(masked_matmul(
            x, w, apply_sr=False, impl="ref") ** 2))(x)
    bskip = measured_backward_skip_fraction(rows)
    assert bskip is not None and 0.0 <= bskip < 1.0
    assert measured_backward_skip_fraction([]) is None

    rec = LayerRecord(kind="fc", name="l", macs=10**12,
                      in_elems=10, w_elems=10, out_elems=10)
    base = spring_eval([rec], 1, training=True,
                       act_sparsity=0.0, w_sparsity=0.0)
    meas = spring_eval([rec], 1, training=True, act_sparsity=0.0,
                       w_sparsity=0.0, backward_skip_fraction=0.5)
    # fwd 1x unscaled + bwd 2x at (1-0.5): 2/3 of the dense-training time
    np.testing.assert_allclose(meas.time_s, base.time_s * (2.0 / 3.0),
                               rtol=1e-6)
