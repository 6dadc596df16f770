"""Shared machinery of the SPRING on-chip benchmark.

Everything here is found by name: a cell of ``BENCHMARK.json`` names its
configuration (``configs/<config>.json``) and its traffic
(``traffic/<traffic>.json``); the traffic names its driver
(``drivers/<driver>.py``); each per-layer metric has its reader
(``metrics/<metric>.py``); each cell has its correctness limits
(``checks/<cell>.json``); each configuration has its plain float32
reference (``reference/<config>.py``).  Adding a cell, a configuration, a
traffic mix or a metric adds files and entries; no file here changes.

Nothing in this module touches JAX when it is imported.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from pathlib import Path
from typing import Any, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a missing file, ...)."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a benchmark file by path (file names may hold '-' and '.')."""
    name = "spring_bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix("").parts)
    name = "".join(c if c.isalnum() else "_" for c in name)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with every file it names loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    checks: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    bench_dir: Path

    def module(self, kind: str, name: str):
        path = self.bench_dir / kind / f"{name}.py"
        if not path.is_file():
            raise BenchError(f"{kind}/{name}.py not found under {self.bench_dir}")
        return load_module(path)

    def driver(self):
        return self.module("drivers", self.traffic["driver"])

    def reference(self):
        return self.module("reference", self.config_name)

    def work(self, name: str):
        return self.module("work", name)


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def load_cell(workload: str, manifest: Path = ROOT / "BENCHMARK.json",
              bench_dir: Path = BENCH_DIR) -> Cell:
    if not manifest.is_file():
        raise BenchError(f"no {manifest.name} at {manifest.parent}")
    bench = load_json(manifest)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfile = manifest.parent / configs[w["config"]]["file"]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, workload, names)]
    checks_path = bench_dir / "checks" / f"{workload}.json"
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=load_json(cfile),
        traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        checks=load_json(checks_path) if checks_path.is_file() else {},
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


# -- devices -----------------------------------------------------------------


def keep_host_backend() -> None:
    """Keep JAX's CPU backend beside the accelerator (the references run
    their optimizer there) where ``JAX_PLATFORMS`` names platforms
    without it.  Call before JAX starts."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"



def require_chips(chips: int) -> list:
    """The devices a cell runs on; a run without a TPU, or with fewer
    chips than the cell asks for, fails instead of falling back."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"cell asks for {chips} chips, {len(devices)} visible")
    return devices[:chips]


def device_record(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def peaks_for(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = load_json(bench_dir / "peaks.json")
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


class CompileClock:
    """Backend compile seconds and persistent-cache hits and misses, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


def program_config(cell: Cell, base):
    """The program's config object for this cell: the registry's published
    config with the cuts of the configuration file applied, checked
    against the sizes the file states."""
    prog = cell.config["program"]
    cfg = dataclasses.replace(base, **prog.get("replace", {}))
    for path, want in prog.get("expect", {}).items():
        got = cfg
        for part in path.split("."):
            got = getattr(got, part)
        if got != want:
            raise BenchError(f"{cell.config_name}: program {path}={got!r}, "
                             f"configuration file states {want!r}")
    return cfg


# -- statistics and checks ---------------------------------------------------


def judge(readings: dict, limits: dict) -> tuple[bool, list]:
    """Compare each number that the cell's checks give a limit with that
    limit.  Returns (all within, rows); a limit with no reading, or a
    reading that is missing or not finite, fails.  Numbers the checks give
    no limit are not compared (``PERF.md`` names them with their
    readings)."""
    rows, ok = [], bool(limits)
    for name, limit in limits.items():
        value = readings.get(name)
        ok &= value is not None and math.isfinite(value) and value <= limit
        rows.append({"name": name, "value": value, "limit": limit})
    return ok, rows


def roofline_share(seconds: float, calls, peaks: dict) -> Optional[float]:
    """Percent of the roofline over ``calls``, a list of (flops, bytes):
    the least time the chip could take for each call (the larger of its
    flops at the bf16 peak and its bytes at HBM bandwidth), summed, over
    the device time the kernel took.  None where it took none."""
    if seconds <= 0:
        return None
    least = sum(max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
                for f, b in calls)
    return 100.0 * least / seconds
