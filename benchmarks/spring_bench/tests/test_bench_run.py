"""Whole runs of throwaway tiny cells on the CPU, with the look for a chip
skipped: the result line's schema, a cell added from a temporary
directory by new files alone, the refusal without a TPU or without the
program, and ``correct`` coming out false when the timed path is broken
underneath."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import run_cell

BENCH = Path(run_cell.__file__).resolve().parent
ROOT = BENCH.parents[1]

@pytest.fixture
def no_persistent_cache():
    """A run turns JAX's persistent compilation cache on; keep it off for
    the rest of this test process."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    yield
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    compilation_cache.reset_cache()


def run_tiny(tiny_bench, workload, trace=False, seconds=0.5):
    manifest, bench = tiny_bench()
    return run_cell.run(workload, 2**33 + 11, seconds, trace, manifest=manifest,
                        bench_dir=bench, require_chip=False, peaks_kind="TPU v5 lite")


@pytest.mark.parametrize("workload,trace", [("tiny.train.qs", False),
                                            ("tiny.train.qs", True),
                                            ("tiny.train.dense", False)])
def test_result_line_schema(tiny_bench, no_persistent_cache, workload, trace):
    r = run_tiny(tiny_bench, workload, trace)
    assert list(r)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in r
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for name, m in r["metrics"].items():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    if trace:
        assert {"busy_s", "window_s"} <= set(r["device"])
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
        # read from the host clock and counters; a CPU trace has no TPU ops,
        # so the device readers find nothing and their metrics are left out
        assert {"tiny_steps.train", "host_ms_per_step.train", "mfu.train"} <= set(r["metrics"])
        assert "masked_matmul_roofline.train" not in r["metrics"]
    else:
        assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for row in r["checks"]:
        assert set(row) == {"name", "value", "limit"} and row["value"] <= row["limit"]
    json.dumps(r)


def _broken_train(monkeypatch, fault):
    from repro.runtime import train as train_mod

    real = train_mod.make_train_step

    def make(*a, **k):
        step = real(*a, **k)

        def unchanged(state, batch):
            return state, step(state, batch)[1]

        def half_batch(state, batch):
            return step(state, {"tokens": batch["tokens"][: batch["tokens"].shape[0] // 2]})

        return {"unchanged": unchanged, "half_batch": half_batch}[fault]

    monkeypatch.setattr(train_mod, "make_train_step", make)


@pytest.mark.parametrize("workload", ["tiny.train.qs", "tiny.train.dense"])
@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(tiny_bench, no_persistent_cache,
                                            monkeypatch, fault, workload):
    _broken_train(monkeypatch, fault)
    r = run_tiny(tiny_bench, workload)
    assert r["correct"] is False
    assert any(row["value"] > row["limit"] for row in r["checks"])


def _run_script(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/spring_bench/run_cell.py", "--workload",
         "mamba2-780m.train.qs", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_non_zero_with_no_result():
    p = _run_script(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_exit_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "spring_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_script(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no program sources" in p.stderr
