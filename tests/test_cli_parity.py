"""CLI parity shims (ISSUE 5 satellite): every pre-redesign flag
spelling resolves to the same RunSpec as its ``--set`` form, with a
DeprecationWarning; and no launcher may carry an argparse option that is
not backed by a RunSpec field (the coverage test the CI spec job runs)."""

import warnings

import pytest

import repro.launch.dryrun as launch_dryrun
import repro.launch.serve as launch_serve
import repro.launch.train as launch_train
from repro.api.cli import OPERATIONAL_OPTIONS, spec_from_args
from repro.api.spec import field_paths

pytestmark = pytest.mark.spec

LAUNCHERS = {
    "train": launch_train,
    "serve": launch_serve,
    "dryrun": launch_dryrun,
}


def _spec(mod, run, argv, warn=True):
    args = mod.build_parser().parse_args(argv)
    return spec_from_args(run, args, mod.LEGACY_FLAGS, warn=warn)


# -- legacy spelling == --set spelling, with a DeprecationWarning ------------

PARITY_CASES = [
    ("train", ["--stash", "stash"], ["--set", "memstash.policy=stash"]),
    ("train", ["--kernel-impl", "ref,ssd_scan=jnp"],
     ["--set", "kernels.policy=ref,ssd_scan=jnp"]),
    ("train", ["--backward-sparsity", "none"],
     ["--set", "sparsity.backward=none"]),
    ("train", ["--arch", "qwen2-7b", "--reduced", "--steps", "7",
               "--batch", "2", "--seq", "16", "--mode", "quant",
               "--lr", "0.01", "--fixed-point-weights",
               "--ckpt-dir", "/tmp/x", "--ckpt-every", "5"],
     ["--set", "arch.id=qwen2-7b", "--set", "arch.reduced=true",
      "--set", "train.steps=7", "--set", "shape.batch=2",
      "--set", "shape.seq=16", "--set", "numerics.mode=quant",
      "--set", "optimizer.lr=0.01",
      "--set", "numerics.fixed_point_weights=true",
      "--set", "train.ckpt_dir=/tmp/x", "--set", "train.ckpt_every=5"]),
    ("serve", ["--slots", "2", "--queue", "6"],
     ["--set", "serving.slots=2", "--set", "serving.queue=6"]),
    ("serve", ["--sample", "--seed", "3", "--static"],
     ["--set", "serving.greedy=false", "--set", "seeds.seed=3",
      "--set", "serving.static=true"]),
    ("serve", ["--kernel-impl", "ref", "--mode", "quant_sparse",
               "--prompt-len", "6", "--gen", "3", "--batch", "2"],
     ["--set", "kernels.policy=ref", "--set", "numerics.mode=quant_sparse",
      "--set", "shape.prompt_len=6", "--set", "shape.gen=3",
      "--set", "shape.batch=2"]),
    ("dryrun", ["--arch", "qwen2-7b", "--shape", "train_4k",
                "--mesh", "multi", "--mode", "quant_sparse",
                "--backward-sparsity", "none", "--kernel-impl", "ref",
                "--layout", "fsdp", "--seq-parallel", "--cache-int8",
                "--quant-opt", "--variant", "v1", "--microbatch", "4",
                "--probe-density", "0.25", "--no-unrolled-cost",
                "--bf16-logits", "--remat-policy", "block_io"],
     ["--set", "arch.id=qwen2-7b", "--set", "shape.cell=train_4k",
      "--set", "shape.mesh=multi", "--set", "numerics.mode=quant_sparse",
      "--set", "sparsity.backward=none", "--set", "kernels.policy=ref",
      "--set", "shape.layout=fsdp", "--set", "shape.seq_parallel=true",
      "--set", "serving.int8_cache=true", "--set", "dryrun.quant_opt=true",
      "--set", "dryrun.variant=v1", "--set", "shape.microbatch=4",
      "--set", "sparsity.probe_density=0.25",
      "--set", "dryrun.cost_unrolled=false",
      "--set", "arch.bf16_logits=true",
      "--set", "arch.remat_policy=block_io"]),
]


@pytest.mark.parametrize("run,legacy_argv,set_argv", PARITY_CASES,
                         ids=[f"{r}-{i}" for i, (r, _, _) in
                              enumerate(PARITY_CASES)])
def test_legacy_flags_resolve_to_same_spec_with_warning(run, legacy_argv,
                                                        set_argv):
    mod = LAUNCHERS[run]
    with pytest.warns(DeprecationWarning, match="--set"):
        legacy = _spec(mod, run, legacy_argv)
    new = _spec(mod, run, set_argv)
    assert legacy == new
    assert legacy.spec_hash() == new.spec_hash()
    # provenance still distinguishes the layers
    assert any(v.startswith("legacy:") for v in legacy.provenance.values())
    assert any(v.startswith("set:") for v in new.provenance.values())


def test_legacy_remat_policy_full_is_a_noop():
    """Preserved quirk: the old dryrun --remat-policy full never replaced
    the arch config, so the shim must not either."""
    with pytest.warns(DeprecationWarning):
        legacy = _spec(launch_dryrun, "dryrun", ["--remat-policy", "full"])
    assert legacy == _spec(launch_dryrun, "dryrun", [])
    assert legacy.arch.remat_policy == ""


def test_paired_boolean_flags_last_on_command_line_wins():
    """--greedy/--sample share one argparse dest (like the old parser),
    so the last spelling typed wins regardless of declaration order."""
    with pytest.warns(DeprecationWarning):
        spec = _spec(launch_serve, "serve", ["--sample", "--greedy"])
    assert spec.serving.greedy is True
    with pytest.warns(DeprecationWarning):
        spec = _spec(launch_serve, "serve", ["--greedy", "--sample"])
    assert spec.serving.greedy is False
    assert spec.provenance["serving.greedy"] == "legacy:--sample"


def test_dryrun_bare_invocation_still_errors(capsys):
    """The pre-RunSpec dryrun CLI required --arch/--shape; a bare
    invocation must not silently compile the default cell."""
    with pytest.raises(SystemExit) as exc:
        launch_dryrun.main([])
    assert exc.value.code == 2
    assert "arch.id" in capsys.readouterr().err


def test_dryrun_explain_reports_the_executed_spec(capsys):
    """--explain must show the spec the run would use (arch.reduced=None
    resolves run-conditionally in the resolver, so CLI and API agree) —
    and still enforce the arch.id/shape.cell guard."""
    rc = launch_dryrun.main(["--set", "arch.id=llama3.2-1b",
                             "--set", "shape.cell=decode_32k", "--explain"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "arch.reduced = None  [default]" in out
    with pytest.raises(SystemExit):  # guard still applies under --explain
        launch_dryrun.main(["--explain"])


def test_set_wins_over_legacy_flag():
    with pytest.warns(DeprecationWarning):
        spec = _spec(launch_train, "train",
                     ["--mode", "quant", "--set", "numerics.mode=dense"])
    assert spec.numerics.mode == "dense"


def test_serve_cli_base_layer_keeps_historical_batch():
    """The serve adapter pins its pre-RunSpec default (--batch 4) as a
    base layer; file/env/CLI layers still override it."""
    args = launch_serve.build_parser().parse_args([])
    spec = spec_from_args("serve", args, launch_serve.LEGACY_FLAGS,
                          base=launch_serve.CLI_BASE)
    assert spec.shape.batch == 4
    assert spec.provenance["shape.batch"] == "launcher-default"
    args = launch_serve.build_parser().parse_args(["--set", "shape.batch=6"])
    assert spec_from_args("serve", args, launch_serve.LEGACY_FLAGS,
                          base=launch_serve.CLI_BASE).shape.batch == 6


def test_no_warning_without_legacy_flags(recwarn):
    _spec(launch_train, "train", ["--set", "train.steps=3"])
    assert not [w for w in recwarn.list
                if issubclass(w.category, DeprecationWarning)]


# -- coverage: every launcher option is RunSpec-backed -----------------------


@pytest.mark.parametrize("name,mod", sorted(LAUNCHERS.items()))
def test_launcher_options_all_backed_by_runspec_fields(name, mod):
    """The CI spec job's growth guard: a launcher may only carry
    operational options (--spec/--set/--json/--out/...) and declared
    LegacyFlag shims, each shim pointing at a real RunSpec field — new
    knobs must become RunSpec fields first."""
    legacy_options = {lf.option for lf in mod.LEGACY_FLAGS}
    for lf in mod.LEGACY_FLAGS:
        assert lf.path in field_paths(), (name, lf.option, lf.path)
    ap = mod.build_parser()
    for action in ap._actions:
        for opt in action.option_strings:
            if not opt.startswith("--"):
                continue
            assert opt in OPERATIONAL_OPTIONS or opt in legacy_options, (
                f"{name}: argparse option {opt} is not backed by a RunSpec "
                f"field — add a field to repro.api.spec and declare a "
                f"LegacyFlag (or use --set)")


def test_examples_flags_are_runspec_backed():
    """The examples' convenience flags must also map onto RunSpec fields
    (they share the LegacyFlag machinery, minus the deprecation)."""
    import importlib.util
    import pathlib
    import sys

    for name in ("serve_batched", "train_lm"):
        path = pathlib.Path(__file__).parent.parent / "examples" / f"{name}.py"
        ispec = importlib.util.spec_from_file_location(f"exflags_{name}", path)
        mod = importlib.util.module_from_spec(ispec)
        sys.modules[ispec.name] = mod
        ispec.loader.exec_module(mod)
        for lf in mod.FLAGS:
            assert lf.path in field_paths(), (name, lf.option)


# -- launchers: compile cache and exit status --------------------------------


class _FakeSession:
    result: dict = {}

    def __init__(self, spec, **_):
        pass

    def run(self):
        return dict(self.result)


@pytest.mark.parametrize("finite", [True, False])
def test_train_launcher_exits_nonzero_on_nonfinite_loss(monkeypatch, finite):
    loss = 1.0 if finite else float("nan")
    fake = type("Fake", (_FakeSession,), {"result": {
        "losses": [2.0, loss], "first_loss": 2.0, "last_loss": loss,
        "slow_steps": 0, "spec_hash": "x", "state": None}})
    monkeypatch.setattr(launch_train, "TrainSession", fake)
    monkeypatch.setattr(launch_train, "enable_compile_cache", lambda: "")
    assert launch_train.main(["--set", "train.steps=2"]) == (0 if finite else 1)


@pytest.mark.parametrize("finite", [True, False])
def test_serve_launcher_exits_nonzero_on_nonfinite_logits(monkeypatch, finite):
    fake = type("Fake", (_FakeSession,), {"result": {
        "prefill_s": 0.1, "decode_s": 0.1, "tokens_per_s": 1.0,
        "finite": finite, "engine": False, "generated": [],
        "spec_hash": "x"}})
    monkeypatch.setattr(launch_serve, "ServeSession", fake)
    monkeypatch.setattr(launch_serve, "enable_compile_cache", lambda: "")
    assert launch_serve.main([]) == (0 if finite else 1)


def test_compile_cache_keeps_env_dir_else_uses_checkout(monkeypatch, tmp_path):
    import jax

    from repro.runtime import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == path
        assert (compile_cache.CHECKOUT_CACHE.parent / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
