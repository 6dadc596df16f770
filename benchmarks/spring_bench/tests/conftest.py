"""Shared pieces of the benchmark's CPU tests: the benchmark's own files
on the import path, and a throwaway benchmark of tiny cells in a
temporary directory, built only from new files and new entries."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MAMBA = {
    "arch": "mamba2-780m", "source": "the registry's reduced mamba2-780m preset",
    "d_model": 64, "n_layer": 4, "vocab_size": 512, "tie_embeddings": True,
    "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4, "expand": 2,
                "headdim": 32, "ngroups": 1},
    # the program's RMSNorm eps, so that the tiny cells read the harness
    # and its faults, not the program's departure from the published 1e-5
    "rms_norm_eps": 1e-6, "reduced": [],
    "program": {"arch": "mamba2-780m", "preset": "reduced", "replace": {},
                "expect": {"d_model": 64, "n_layers": 4, "vocab": 512,
                           "ssm.d_inner": 128, "ssm.n_heads": 4, "ssm.d_state": 16}},
}

#: a per-layer metric that only the throwaway benchmark has: its reader
#: is a new file and its entry a new one
TINY_METRIC = '''"""Steps completed in the window."""


def read(run):
    return float(run.counters["steps"])
'''


def tiny_train_traffic(mode: str) -> dict:
    base = json.loads((BENCH / "traffic" / "train.qs.json").read_text())
    return dict(base, mode=mode, batch=2, seq=64)


#: limits of the tiny cells, set from their CPU readings (seeds 1-3): the
#: quant_sparse program reads loss 2e-6..8e-6, grad 3e-5..5e-5, change
#: 1e-4..2.2e-4 and its dense control loss 1.7e-4..6.2e-4, grad
#: 2.4e-3..6.2e-3; the dense program's int8 control reads loss
#: 1.1e-3..2.0e-3 against its own 1.7e-4..6.2e-4
TINY_LIMITS = {
    "tiny.train.qs": {"loss_gap": 5e-5, "grad_gap": 5e-4, "change_gap": 1e-3},
    "tiny.train.dense": {"loss_gap": 9e-4, "grad_gap": 2e-2, "change_gap": 2e-2},
}


def make_bench(root: Path, limits: dict = TINY_LIMITS) -> tuple[Path, Path]:
    """A checkout-like tree at ``root``: the benchmark's files copied, plus
    a tiny configuration, traffic, checks, a per-layer metric and a
    manifest naming them."""
    bench = root / "benchmarks" / "spring_bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(ROOT / "src")
    files = {
        "configs/tiny-mamba2.json": TINY_MAMBA,
        "traffic/tiny.train.qs.json": tiny_train_traffic("quant_sparse"),
        "traffic/tiny.train.dense.json": tiny_train_traffic("dense"),
    }
    for name, lim in limits.items():
        files[f"checks/{name}.json"] = {"limits": lim}
    for rel, obj in files.items():
        (bench / rel).write_text(json.dumps(obj))
    (bench / "metrics" / "tiny_steps.train.py").write_text(TINY_METRIC)
    # the references and work functions are found by configuration name
    for kind in ("reference", "work"):
        shutil.copy(bench / kind / "mamba2-780m.py", bench / kind / "tiny-mamba2.py")
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"] = [
        {"name": "tiny-mamba2", "source": "test", "file": "benchmarks/spring_bench/configs/tiny-mamba2.json",
         "reduced": [], "why": "test"}]
    manifest["workloads"] = [
        {"name": "tiny.train.qs", "config": "tiny-mamba2", "traffic": "tiny.train.qs", "chips": 1, "why": "test"},
        {"name": "tiny.train.dense", "config": "tiny-mamba2", "traffic": "tiny.train.dense", "chips": 1, "why": "test"}]
    rename = {"mamba2-780m.train.qs": ["tiny.train.qs", "tiny.train.dense"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [t for w in m["workloads"] for t in rename.get(w, [])]
    manifest["per_layer"].append({
        "name": "tiny_steps.train", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "session", "moves": "train_tokens_per_s",
        "workloads": ["tiny.train.qs", "tiny.train.dense"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root / "BENCHMARK.json", bench


@pytest.fixture
def tiny_bench(tmp_path):
    return lambda limits=TINY_LIMITS: make_bench(tmp_path, limits)
