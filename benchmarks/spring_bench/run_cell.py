#!/usr/bin/env python3
"""Run one cell of the SPRING benchmark once, on the chips of this machine.

    python3 benchmarks/spring_bench/run_cell.py --workload <name> \\
        --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is an entry of ``BENCHMARK.json``;
every file it needs is found by name (see ``harness.py``).  The run makes
its weights and inputs from ``--seed``, warms up every shape the window
uses (set-up), measures for ``--seconds``, then checks what the timed
path produced against the plain float32 reference.  ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), and last ``checks``, each number compared with its
limit; the checks are also the last lines of standard error.  A run that
finds no TPU, fewer chips than the cell asks for, or no program sources
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

import harness  # noqa: E402


def per_layer_metrics(cell, out: dict, peaks: dict, e2e: dict) -> dict:
    """Each per-layer metric of the cell, from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    run = types.SimpleNamespace(
        cell=cell, trace=out["trace"], counters=out["counters"],
        spans=out["spans"], detail=out["detail"], peaks=peaks, end_to_end=e2e,
        chips=out["device"]["count"], work=cell.work)
    got = {}
    for m in cell.per_layer:
        value = cell.module("metrics", m["name"]).read(run)
        if value is not None:
            got[m["name"]] = {"value": value, "unit": m["unit"]}
    return got


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        manifest: Path = harness.ROOT / "BENCHMARK.json",
        bench_dir: Path = harness.BENCH_DIR, require_chip: bool = True,
        peaks_kind: str = "") -> dict:
    """One run of one cell; returns the result object (the last line).
    Tests pass ``require_chip=False`` and a ``peaks_kind`` to drive the
    rest of a run on the CPU."""
    src = manifest.parent / "src"
    if not (src / "repro").is_dir():
        raise harness.BenchError(f"no program sources under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    cell = harness.load_cell(workload, manifest, bench_dir)
    harness.keep_host_backend()

    import jax

    if require_chip:
        devices = harness.require_chips(cell.chips)
    else:
        devices = jax.devices()[:cell.chips]
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = harness.peaks_for(peaks_kind or devices[0].device_kind, bench_dir)
    clock = harness.CompileClock()
    out = cell.driver().run(cell, seed, seconds, trace, clock, devices)
    e2e = dict(out["end_to_end"], setup_s=out["window_start"] - T_START)
    device = dict(out["device"])
    result = {"correct": bool(out["correct"]), "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        tr = out["trace"]
        result["metrics"] = per_layer_metrics(cell, out, peaks, e2e)
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["device"] = device
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["counters"] = dict(out["counters"], compile_s=clock.seconds,
                              cache_hits=clock.hits, cache_misses=clock.misses)
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
        line = json.dumps(result, allow_nan=False)
    except harness.BenchError as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    except ValueError as e:  # a number that JSON cannot hold (a metric with no samples)
        print(f"run_cell: no result: {e}", file=sys.stderr)
        return 2
    for row in result["checks"]:
        print(f"check {row['name']} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
