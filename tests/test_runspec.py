"""RunSpec seal (ISSUE 5): round-trip bit-exactness across every
registered arch x run mode, layered resolution with provenance,
unknown-field rejection with did-you-mean, spec emission in session
artifacts, and resolver parity with the legacy launcher surfaces."""

import dataclasses
import json

import pytest

from repro.api.spec import (
    ENV_FIELDS,
    RunSpec,
    SpecError,
    build_spec,
    field_paths,
)
from repro.configs import ARCHS

pytestmark = pytest.mark.spec

ALL_ARCHS = sorted(ARCHS)
RUNS = ("train", "serve", "dryrun")


# -- round-trip seal ----------------------------------------------------------


@pytest.mark.parametrize("arch_id", ALL_ARCHS)
@pytest.mark.parametrize("run", RUNS)
def test_roundtrip_bit_identical_per_arch_and_mode(arch_id, run):
    """RunSpec -> to_json -> from_json -> resolve() is bit-identical for
    every registered arch x {train, serve, dryrun}."""
    spec = build_spec(run, use_env=False,
                      overrides=[("arch.id", arch_id, "test")])
    text = spec.to_json()
    again = RunSpec.from_json(text)
    assert again == spec
    assert again.to_json() == text
    assert again.spec_hash() == spec.spec_hash()
    r1, r2 = spec.resolve(), again.resolve()
    # the resolved objects the step builders consume must be identical
    assert r1.step == r2.step
    assert r1.spring == r2.spring
    assert r1.config == r2.config
    assert r1.view == r2.view
    assert r1.memstash == r2.memstash
    assert r1.memstash_policy == r2.memstash_policy


def test_roundtrip_preserves_non_defaults():
    spec = build_spec("serve", use_env=False, sets=[
        "serving.slots=2", "serving.queue=7", "numerics.mode=quant_sparse",
        "kernels.policy=ref,ssd_scan=jnp", "shape.microbatch=none",
        "sparsity.probe_density=0.25", "seeds.seed=11",
    ])
    again = RunSpec.from_json(spec.to_json())
    assert again.serving.slots == 2 and again.serving.queue == 7
    assert again.kernels.policy == "ref,ssd_scan=jnp"
    assert again.shape.microbatch is None
    assert again == spec


def test_canonical_json_is_sorted_and_stable():
    spec = build_spec("train", use_env=False)
    d = json.loads(spec.to_json())
    assert list(d) == sorted(d)
    # hash is a pure function of the canonical form
    assert spec.spec_hash() == RunSpec.from_dict(d).spec_hash()


# -- unknown fields / invalid values -----------------------------------------


def test_unknown_field_rejected_with_suggestion():
    with pytest.raises(SpecError, match="numerics.mode"):
        RunSpec.from_dict({"numerics": {"mod": "quant"}})
    with pytest.raises(SpecError, match="did you mean"):
        RunSpec.from_dict({"numeric": {"mode": "quant"}})
    with pytest.raises(SpecError, match="did you mean"):
        build_spec(sets=["serving.slotss=2"], use_env=False)


def test_invalid_choice_rejected_with_suggestion():
    with pytest.raises(SpecError, match="quant_sparse"):
        build_spec(sets=["numerics.mode=quant_spars"], use_env=False)
    with pytest.raises(SpecError, match="memstash"):
        build_spec(sets=["memstash.policy=stashh"], use_env=False)
    with pytest.raises(SpecError, match="kernels.policy"):
        build_spec(sets=["kernels.policy=ssd_scanx=jnp"], use_env=False)
    with pytest.raises(SpecError, match="block_io"):
        build_spec(sets=["arch.remat_policy=blockio"], use_env=False)


def test_type_errors_are_spec_errors():
    with pytest.raises(SpecError, match="integer"):
        RunSpec.from_dict({"shape": {"batch": "eight"}})
    with pytest.raises(SpecError, match="boolean"):
        build_spec(sets=["arch.reduced=maybe"], use_env=False)


# -- layered resolution + provenance -----------------------------------------


def test_layer_precedence_file_env_cli(tmp_path):
    p = tmp_path / "s.json"
    p.write_text(json.dumps({
        "numerics": {"mode": "quant"}, "shape": {"batch": 4},
        "train": {"steps": 9}}))
    env = {"SPRING_MODE": "quant_sparse", "SPRING_SEED": "5"}
    spec = build_spec("train", spec_file=str(p), environ=env,
                      sets=["numerics.mode=dense"])
    # CLI --set > env > file; untouched fields keep file/default values
    assert spec.numerics.mode == "dense"
    assert spec.seeds.seed == 5
    assert spec.shape.batch == 4 and spec.train.steps == 9
    prov = spec.provenance
    assert prov["numerics.mode"].startswith("set:")
    assert prov["seeds.seed"] == "env:SPRING_SEED"
    assert prov["shape.batch"].startswith("file:")
    assert prov["train.ckpt_dir"] == "default"


def test_env_fields_cover_documented_vars():
    for var, path in ENV_FIELDS.items():
        assert var.startswith("SPRING_")
        assert path in field_paths()
    spec = build_spec(environ={"SPRING_SET": "shape.seq=999"})
    assert spec.shape.seq == 999


def test_provenance_excluded_from_equality_and_serialization():
    a = build_spec("train", use_env=False)
    b = build_spec("train", use_env=False, environ={})
    object.__setattr__(b, "provenance", {"run": "somewhere-else"})
    assert a == b
    assert "provenance" not in a.to_dict()


# -- resolver parity with the legacy surfaces --------------------------------


def test_resolver_matches_legacy_train_stepconfig():
    """train_spec(...) resolves to the StepConfig train_loop used to
    build by hand (ISSUE 5 tentpole: one resolution path)."""
    from repro.api.sessions import train_spec
    from repro.core.fixedpoint import SPRING_FORMAT
    from repro.core.spring_ops import MODES
    from repro.kernels.registry import KernelPolicy
    from repro.memstash.config import MemstashConfig
    from repro.optim.optimizers import OptimizerConfig

    spec = train_spec("llama3.2-1b", mode="quant", lr=1e-2,
                      fixed_point_weights=True, kernel_impl="ref",
                      backward_sparsity="none", stash="stash")
    r = spec.resolve()
    assert r.spring == dataclasses.replace(
        MODES["quant"], kernels=KernelPolicy.parse("ref"), backward_sparsity="none")
    assert r.step.spring == r.spring
    assert r.step.memstash == MemstashConfig(policy="stash")
    assert r.step.optimizer == OptimizerConfig(
        kind="adamw", lr=1e-2, warmup_steps=10, weight_format=SPRING_FORMAT)
    # explicit stash re-routes the LM residual checkpoints
    assert r.config.remat_policy == "stash"


def test_resolver_matches_legacy_serving_config():
    from repro.api.sessions import serve_spec
    from repro.core.spring_ops import MODES

    for mode in ("dense", "quant", "quant_sparse"):
        r = serve_spec("llama3.2-1b", mode=mode).resolve()
        assert r.spring == dataclasses.replace(
            MODES[mode], stochastic=False)
        assert r.step.optimizer.warmup_steps == 0  # serving OptimizerConfig()


def test_resolver_dryrun_microbatch_defaults():
    from repro.api.sessions import dryrun_spec
    from repro.api.spec import DEFAULT_TRAIN_MICROBATCH, TRAIN_MICROBATCH_OVERRIDES

    assert dryrun_spec("qwen2-7b", "train_4k").resolve().step.microbatch \
        == DEFAULT_TRAIN_MICROBATCH
    assert dryrun_spec("olmoe-1b-7b", "train_4k").resolve().step.microbatch \
        == TRAIN_MICROBATCH_OVERRIDES["olmoe-1b-7b"]
    assert dryrun_spec("qwen2-7b", "decode_32k").resolve().step.microbatch is None
    assert dryrun_spec("qwen2-7b", "train_4k",
                       microbatch=4).resolve().step.microbatch == 4


def test_resolver_dryrun_optimizer_parity_with_legacy_run_cell():
    """Dryrun lowers the optimizer *kind* only (legacy run_cell built
    OptimizerConfig(kind="adamw")): lr/warmup must not leak into the
    lowered program, preserving bit-parity with pre-RunSpec artifacts."""
    from repro.api.sessions import dryrun_spec
    from repro.optim.optimizers import OptimizerConfig

    r = dryrun_spec("qwen2-7b", "train_4k").resolve()
    assert r.step.optimizer == OptimizerConfig(kind="adamw")
    assert r.step.optimizer.warmup_steps == 0


def test_spring_set_env_supports_comma_bearing_values():
    """SPRING_SET entries are ';'-separated so the documented multi-op
    KernelPolicy grammar survives the env layer."""
    spec = build_spec(environ={
        "SPRING_SET": "kernels.policy=ref,ssd_scan=jnp;shape.batch=16"})
    assert spec.kernels.policy == "ref,ssd_scan=jnp"
    assert spec.shape.batch == 16
    assert spec.resolve().kernel_policy.describe() == "ref,ssd_scan=jnp"


def test_resolver_dryrun_layout_rules():
    from repro.api.sessions import dryrun_spec

    base = dryrun_spec("qwen2-7b", "train_4k")
    assert base.resolve().step.rules_override == ()
    fsdp = dryrun_spec("qwen2-7b", "train_4k", layout="fsdp",
                       seq_parallel=True)
    rules = dict(fsdp.resolve().step.rules_override)
    assert "seq" in rules and "batch" in rules and "w_qkv" in rules


def test_arch_reduced_null_is_run_conditional_in_resolver():
    """arch.reduced=null: train/serve resolve the reduced smoke config,
    dryrun the published full config — identically for CLI and API
    callers (no launcher-only correction)."""
    from repro.configs import get_arch

    arch = get_arch("llama3.2-1b")
    train = build_spec("train", use_env=False)
    assert train.arch.reduced is None
    assert train.resolve().config == arch.reduced()
    dry = build_spec("dryrun", use_env=False)
    assert dry.resolve().config == arch.config
    # explicit values still win in both directions
    assert build_spec("dryrun", use_env=False,
                      sets=["arch.reduced=true"]).resolve().config \
        == arch.reduced()
    assert build_spec("train", use_env=False,
                      sets=["arch.reduced=false"]).resolve().config \
        == arch.config


def test_stochastic_auto_rule():
    """auto: SR on for train/dryrun (the paper's convergence device),
    nearest for serve (batch invariance); on/off force it."""
    from repro.api.sessions import serve_spec, train_spec

    assert train_spec("llama3.2-1b", mode="quant").resolve().spring.stochastic
    assert not serve_spec("llama3.2-1b", mode="quant").resolve().spring.stochastic
    off = build_spec("train", use_env=False,
                     sets=["numerics.mode=quant", "numerics.stochastic=off"])
    assert off.resolve().spring.stochastic is False
    on = build_spec("serve", use_env=False,
                    sets=["numerics.mode=quant", "numerics.stochastic=on"])
    assert on.resolve().spring.stochastic is True


# -- session artifacts embed the spec ----------------------------------------


def test_sessions_embed_canonical_spec():
    from repro.api.sessions import ServeSession, TrainSession, serve_spec, train_spec

    tspec = train_spec("llama3.2-1b", steps=1, batch=2, seq=16)
    tout = TrainSession(tspec).run()
    assert tout["spec_hash"] == tspec.spec_hash()
    assert tout["spec"] == tspec.to_dict()
    assert tout["provenance"]["train.steps"] == "call:train.steps"

    sspec = serve_spec("llama3.2-1b", batch=2, prompt_len=4, gen=2)
    sout = ServeSession(sspec).run()
    assert sout["spec_hash"] == sspec.spec_hash()
    assert sout["spec"]["run"] == "serve"
    # the artifact alone reproduces the run: rebuild the spec from it
    again = RunSpec.from_dict(sout["spec"])
    assert again == sspec


def test_stepconfig_from_runspec_accepts_spec_dict_and_json():
    from repro.runtime.train import StepConfig

    spec = build_spec("train", use_env=False,
                      sets=["numerics.mode=quant_sparse",
                            "sparsity.backward=none"])
    want = spec.resolve().step
    assert StepConfig.from_runspec(spec) == want
    assert StepConfig.from_runspec(spec.to_dict()) == want
    assert StepConfig.from_runspec(spec.to_json()) == want
    # a run artifact embedding its spec (what every session/launcher
    # emits) reproduces the same StepConfig
    artifact = dict(spec.payload(), result={"loss": 1.0})
    assert StepConfig.from_runspec(artifact) == want
    assert StepConfig.from_runspec(json.dumps(artifact)) == want


def test_session_rejects_wrong_run_mode():
    from repro.api.sessions import TrainSession, serve_spec

    with pytest.raises(SpecError, match="run='train'"):
        TrainSession(serve_spec("llama3.2-1b"))


def test_example_specs_validate_and_resolve():
    """Every checked-in example spec must stay loadable + resolvable
    (the CI spec job also runs repro.api.validate over them)."""
    import pathlib

    spec_dir = pathlib.Path(__file__).parent.parent / "examples" / "specs"
    paths = sorted(spec_dir.glob("*.json"))
    assert paths, "examples/specs/ must contain at least one worked example"
    for p in paths:
        spec = RunSpec.from_file(str(p))
        spec.resolve()
        assert RunSpec.from_json(spec.to_json()) == spec
