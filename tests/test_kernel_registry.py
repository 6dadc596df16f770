"""Kernel-dispatch registry: policy semantics, instrumentation, and the
registry-GENERATED parity harness (replaces the hand-enumerated per-op
interpret-vs-ref sweeps — every registered (op, impl) pair runnable on
this backend is cross-checked against its oracle automatically, so a new
kernel cannot land without registering)."""

import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import registry
from repro.kernels.registry import KernelPolicy, compare_outputs, kernel_policy
from repro.kernels.ssd_scan.ops import ssd_scan


# -- policy object ------------------------------------------------------------


def test_policy_parse_global_and_per_op():
    p = KernelPolicy.parse("ref,ssd_scan=jnp")
    assert p.default == "ref"
    assert p.impl_for("ssd_scan") == "jnp"
    assert p.impl_for("masked_matmul") == "ref"
    assert KernelPolicy.parse("").is_auto
    assert KernelPolicy.parse("auto").is_auto


def test_policy_rejects_unknown_impl_names():
    with pytest.raises(ValueError, match="unknown kernel impl"):
        KernelPolicy.parse("cuda")
    with pytest.raises(ValueError, match="unknown kernel impl"):
        KernelPolicy.parse("ssd_scan=fast")
    with pytest.raises(ValueError, match="unknown kernel op"):
        KernelPolicy.parse("not_an_op=ref")
    with pytest.raises(ValueError, match="unknown kernel impl"):
        KernelPolicy(default="bogus")


def test_policy_rejects_unknown_op_names_everywhere():
    """A typo'd op must raise, not silently pin nothing (constructor and
    context-manager paths, not just parse)."""
    with pytest.raises(ValueError, match="unknown kernel op"):
        KernelPolicy(overrides=(("ssd_scn", "jnp"),))
    with pytest.raises(ValueError, match="unknown kernel op"):
        with kernel_policy(ssd_scn="jnp"):
            pass


def test_policy_describe_roundtrips():
    for spec in ("auto", "ref", "interpret,ssd_scan=jnp"):
        assert KernelPolicy.parse(spec).describe() == spec.replace("auto", "auto")
    assert KernelPolicy().describe() == "auto"


# -- context manager + env var ------------------------------------------------


def test_kernel_policy_context_wins_over_auto_and_restores():
    before = registry.current_policy()
    with kernel_policy("ref"):
        assert registry.resolve("ssd_scan").name == "ref"
        # nesting: innermost wins
        with kernel_policy(ssd_scan="jnp"):
            assert registry.resolve("ssd_scan").name == "jnp"
        assert registry.resolve("ssd_scan").name == "ref"
    assert registry.current_policy() == before
    # auto on CPU: ssd -> jnp (vectorized), others -> ref
    assert registry.resolve("ssd_scan").name == "jnp"
    assert registry.resolve("masked_matmul").name == "ref"


def test_kernel_policy_context_restores_on_exception():
    with pytest.raises(RuntimeError):
        with kernel_policy("interpret"):
            raise RuntimeError("boom")
    assert registry.current_policy().is_auto


def test_env_var_policy(monkeypatch):
    monkeypatch.setenv(registry.ENV_VAR, "ssd_scan=ref")
    assert registry.resolve("ssd_scan").name == "ref"
    # the context manager outranks the env var
    with kernel_policy(ssd_scan="jnp"):
        assert registry.resolve("ssd_scan").name == "jnp"
    monkeypatch.setenv(registry.ENV_VAR, "bogus")
    with pytest.raises(ValueError, match="unknown kernel impl"):
        registry.resolve("ssd_scan")


def test_explicit_impl_beats_policy():
    with kernel_policy("ref"):
        assert registry.resolve("ssd_scan", "jnp").name == "jnp"


def test_unknown_names_rejected_at_resolve():
    with pytest.raises(ValueError, match="unknown kernel impl"):
        registry.resolve("ssd_scan", "fast")
    with pytest.raises(KeyError, match="unknown kernel op"):
        registry.resolve("conv9000")


def test_global_default_is_soft_but_per_op_is_strict():
    # masked_matmul registers no "jnp": a global jnp default falls back
    # to auto, a per-op pin raises
    with kernel_policy("jnp"):
        assert registry.resolve("masked_matmul").name == "ref"
    with kernel_policy(masked_matmul="jnp"):
        with pytest.raises(ValueError, match="no 'jnp' implementation"):
            registry.resolve("masked_matmul")


def test_pallas_unavailable_on_cpu_is_an_error():
    assert jax.default_backend() != "tpu"
    with pytest.raises(ValueError, match="not available"):
        registry.resolve("masked_matmul", "pallas")


# -- capability gating (ssd_scan return_state) --------------------------------


def _ssd_inputs(b=1, s=96, h=2, p=32, g=1, n=16):
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 2), (b, s, h)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 3), (h,)) * 0.3)
    bb = jax.random.normal(jax.random.fold_in(key, 4), (b, s, g, n)) / 4
    c = jax.random.normal(jax.random.fold_in(key, 5), (b, s, g, n)) / 4
    return x, dt, a, bb, c


def test_ssd_return_state_rejects_non_jnp_impls_with_clear_error():
    args = _ssd_inputs()
    for impl in ("ref", "interpret"):
        with pytest.raises(ValueError) as ei:
            ssd_scan(*args, impl=impl, return_state=True)
        assert impl in str(ei.value) and "jnp" in str(ei.value)


def test_ssd_return_state_auto_routes_to_jnp():
    args = _ssd_inputs()
    y, state = ssd_scan(*args, return_state=True)  # auto
    assert state.shape == (1, 2, 16, 32)
    assert bool(jnp.all(jnp.isfinite(y)))
    # a soft global default that can't serve the call also routes to jnp
    with kernel_policy("ref"):
        y2, state2 = ssd_scan(*args, return_state=True)
    np.testing.assert_array_equal(np.asarray(state), np.asarray(state2))


# -- dispatch counters + instrumentation metrics ------------------------------


def test_dispatch_counters_accumulate_and_reset():
    from repro.kernels.stochastic_round.ops import stochastic_round

    registry.reset_dispatch_counts()
    x = jnp.ones((64,))
    stochastic_round(x, jnp.uint32(1))
    stochastic_round(x, jnp.uint32(2), impl="interpret")
    counts = registry.dispatch_counts()["stochastic_round"]
    assert counts["ref"] == 1 and counts["interpret"] == 1
    registry.reset_dispatch_counts()
    assert registry.dispatch_counts() == {}


def test_metrics_hooks_record_tile_skip_and_wire_bytes():
    from repro.kernels.mask_compress.ops import mask_pack
    from repro.kernels.masked_matmul.ops import masked_matmul

    x = jnp.zeros((256, 256)).at[:128, :128].set(1.0)
    w = jnp.ones((256, 256))
    with registry.record_kernel_metrics() as rows:
        masked_matmul(x, w, jnp.uint32(0))
        mask_pack(x)
    summary = registry.metric_summary(rows)
    assert 0.0 < summary["masked_matmul"]["tile_skip"] < 1.0
    assert summary["mask_pack"]["wire_bytes"] == 256 * 256 / 32 * 4
    # unaligned length: ceil(n/32) words of wire, NOT the kernel's lane pad
    with registry.record_kernel_metrics() as rows2:
        mask_pack(jnp.ones((1000,)))
    assert registry.metric_summary(rows2)["mask_pack"]["wire_bytes"] == 32 * 4
    # hooks are inert outside the recording block and under tracing
    jax.jit(lambda a, b: masked_matmul(a, b, jnp.uint32(0)))(x, w)


def test_measured_skip_feeds_perfmodel():
    from repro.kernels.masked_matmul.ops import masked_matmul
    from repro.models.cnn import LayerRecord
    from repro.perfmodel.spring_model import measured_skip_fraction, spring_eval

    x = jnp.zeros((256, 256)).at[:128, :128].set(1.0)
    with registry.record_kernel_metrics() as rows:
        masked_matmul(x, jnp.ones((256, 256)), jnp.uint32(0))
    skip = measured_skip_fraction(rows)
    assert skip is not None and 0.0 < skip < 1.0
    assert measured_skip_fraction([]) is None
    # compute-bound synthetic layer: the measured skip must scale the
    # compute term exactly like (1 - skip)
    rec = LayerRecord(kind="fc", name="l", macs=10**12,
                      in_elems=10, w_elems=10, out_elems=10)
    dense = spring_eval([rec], 1, training=False,
                        act_sparsity=0.0, w_sparsity=0.0)
    meas = spring_eval([rec], 1, training=False, act_sparsity=0.0,
                       w_sparsity=0.0, compute_skip_fraction=skip)
    np.testing.assert_allclose(meas.time_s, dense.time_s * (1.0 - skip), rtol=1e-6)


def test_resolution_table_never_raises():
    table = registry.resolution_table(KernelPolicy.parse("pallas"))
    assert set(table) == set(registry.ops())
    auto = registry.resolution_table()
    for op, got in table.items():
        if "pallas" in registry.impls(op):  # registered, unavailable on CPU
            assert str(got).startswith("error"), (op, got)
        else:  # the soft global default falls back to auto
            assert got == auto[op], (op, got)
    assert {op for op in table if "pallas" not in registry.impls(op)} == \
        {"kv_unpack", "mask_unpack"}
    assert auto["ssd_scan"] == "jnp" and auto["masked_matmul"] == "ref"


def test_resolution_table_with_auto_policy_reflects_ambient(monkeypatch):
    """An auto policy argument must not shadow the ambient env policy —
    the dry-run's kernel_impls field reports what the trace actually saw."""
    monkeypatch.setenv(registry.ENV_VAR, "ssd_scan=ref")
    table = registry.resolution_table(KernelPolicy())
    assert table["ssd_scan"] == "ref"


# -- config threading ---------------------------------------------------------


def test_spring_config_policy_reaches_matmul_dispatch():
    from repro.core.spring_ops import QUANT_SPARSE, KeyGen, spring_matmul
    import dataclasses

    registry.reset_dispatch_counts()
    cfg = dataclasses.replace(QUANT_SPARSE,
                              kernels=KernelPolicy.parse("masked_matmul=interpret"))
    x = jnp.round(jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 16) / 64
    y = spring_matmul(x, x, cfg, KeyGen(jax.random.PRNGKey(1)))
    assert y.shape == (64, 64)
    # exactly one dispatch: the config-threading planning resolution must
    # not double-count on top of the wrapper's dispatching resolution
    assert registry.dispatch_counts()["masked_matmul"] == {"interpret": 1}


def test_planning_resolutions_do_not_count_as_dispatches():
    registry.reset_dispatch_counts()
    registry.resolution_table()
    registry.resolve_with(KernelPolicy.parse("ref"), "ssd_scan")
    assert registry.dispatch_counts() == {}


def test_spring_config_use_pallas_is_gone():
    from repro.core.spring_ops import SpringConfig

    assert not hasattr(SpringConfig(), "use_pallas")
    assert isinstance(SpringConfig().kernels, KernelPolicy)


# -- registration completeness ------------------------------------------------


def test_every_kernel_package_registers_an_op():
    """A kernels/<name>/ops.py that registers nothing is a bug: the parity
    harness and the policy machinery would silently skip it."""
    kernels_dir = pathlib.Path(registry.__file__).parent
    packages = sorted(
        d.name for d in kernels_dir.iterdir()
        if d.is_dir() and (d / "ops.py").exists()
    )
    assert packages, "kernel packages not found"
    registered_modules = set()
    for op in registry.ops():
        for kimpl in registry.impls(op).values():
            mod = getattr(kimpl.fn, "__module__", "") or ""
            # partial() wrappers keep the underlying function's module
            fn = getattr(kimpl.fn, "func", kimpl.fn)
            registered_modules.add(getattr(fn, "__module__", mod))
    for pkg in packages:
        assert any(f"repro.kernels.{pkg}." in m for m in registered_modules), (
            f"kernels/{pkg}/ops.py registers no implementation with "
            f"repro.kernels.registry")


def test_capability_table_shape():
    table = registry.capability_table()
    assert set(table) == set(registry.ops())
    for op, impls in table.items():
        oracle = [n for n, row in impls.items() if row["oracle"]]
        assert len(oracle) == 1, f"{op} must declare exactly one oracle"
        assert all(not row["selectable"] for n, row in impls.items()
                   if n == "interpret"), "interpret is explicit-only"


# -- the generated parity harness --------------------------------------------


PAIRS = [(op, impl) for op, impl in registry.parity_pairs()
         if registry.op_spec(op).examples is not None]


@pytest.mark.kernel_parity
@pytest.mark.parametrize("op,impl", PAIRS, ids=[f"{o}-{i}" for o, i in PAIRS])
def test_registry_parity(op, impl):
    """Every registered (op, impl) runnable on this backend matches the
    op's oracle on the op's registered example inputs, under the op's
    registered comparison spec."""
    spec = registry.op_spec(op)
    oracle_fn = registry.impls(op)[spec.oracle].fn
    impl_fn = registry.impls(op)[impl].fn
    for case in spec.examples():
        args, kwargs = case[0], case[1]
        case_cmp = case[2] if len(case) > 2 else None
        want = oracle_fn(*args, **kwargs)
        got = impl_fn(*args, **kwargs)
        compare_outputs(op, got, want, case_cmp)


@pytest.mark.kernel_parity
def test_parity_pairs_cover_all_cpu_impls():
    """The generated suite exercises every non-oracle registered impl that
    is runnable on CPU (pallas is TPU-only and correctly excluded)."""
    covered = set(PAIRS)
    for op in registry.ops():
        spec = registry.op_spec(op)
        for name, kimpl in registry.impls(op).items():
            if name == spec.oracle or not kimpl.parity or not kimpl.available():
                continue
            assert (op, name) in covered, f"({op}, {name}) missing from parity sweep"
