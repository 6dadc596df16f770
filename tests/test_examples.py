"""Smoke tests: each runnable example imports and completes ``main(steps=1)``
under the default backward_sparsity="auto" — the examples are
documentation, so they must stay green (ISSUE 3, satellite 4)."""

import importlib.util
import pathlib
import sys

EXAMPLES_DIR = pathlib.Path(__file__).parent.parent / "examples"


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        f"examples_{name}", EXAMPLES_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_quickstart_main_runs(capsys):
    mod = _load("quickstart")
    mod.main(steps=1)
    out = capsys.readouterr().out
    assert "[P1]" in out and "[P2]" in out and "[train]" in out
    assert "bwd dX" in out  # the backward-sparsity demo line


def test_train_lm_main_runs(tmp_path, capsys):
    mod = _load("train_lm")
    res = mod.main(steps=1, argv=["--ckpt-dir", str(tmp_path / "ck")])
    assert res["last_loss"] is not None
    assert "final:" in capsys.readouterr().out


def test_serve_batched_main_plumbs_engine_flags(capsys):
    """ISSUE 4 satellite: --kernel-impl / --greedy / --seed (and the
    engine's --slots/--queue) reach serve_session from the example CLI."""
    mod = _load("serve_batched")
    out = mod.main(argv=[
        "--batch", "2", "--prompt-len", "6", "--gen", "3", "--slots", "2",
        "--queue", "3", "--mode", "quant_sparse", "--kernel-impl", "ref",
        "--greedy", "--seed", "3",
    ])
    assert out["engine"] and out["finite"]
    assert len(out["per_request"]) == 3
    text = capsys.readouterr().out
    assert "tok/s" in text and "kv:" in text
