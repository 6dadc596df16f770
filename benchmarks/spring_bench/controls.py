#!/usr/bin/env python3
"""Readings of a cell's correctness numbers for sound runs, for the
control and for planted faults, at the cell's own size, several seeds in
one process.  Its readings set the limits in ``checks/<cell>.json``;
``PERF.md`` gives them.  The benchmark's own runs never run this.

    python3 benchmarks/spring_bench/controls.py --workload <name> \\
        --seeds 1,2,3 [--what sound,control,half_batch]

Training cells (``train`` driver):

    sound        the program as the cell runs it
    control      the reference with every product on a per-tensor int8
                 grid, put in the program's place
    dense_path   the program's own bf16 path (``dense``), tried first as
                 the quant_sparse cell's control (it reads closer to the
                 reference than quant_sparse does; see PERF.md)
    half_batch   the program's step on half of each batch (half of the
                 rows, or of the one row's tokens), the mean over the rest
    unchanged    a step that returns its state unchanged: every change
                 norm is 0 (worked out, no run)

Witnesses, for what the sound runs read (``PERF.md`` gives their
readings):

    sr_key       the program with its stochastic-rounding stream drawn
                 from another key: how far two sound runs of the same
                 weights and batches lie apart
    f32_path     the program with every kernel on its jnp lowering and
                 every product at full float32 precision
    eps_program  the reference at the program's RMSNorm eps instead of
                 the configuration's, against the sound program and
                 against the reference itself

Each reading is one JSON line on standard output, with the losses of
its steps, the device, its peak memory so far and the seconds the seed
took.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

import harness  # noqa: E402


def _half(step):
    def half_step(state, batch):
        tok = batch["tokens"]
        tok = tok[: tok.shape[0] // 2] if tok.shape[0] > 1 else tok[:, : tok.shape[1] // 2]
        return step(state, {"tokens": tok})
    return half_step


def program_rms_eps() -> float:
    """The RMSNorm eps the program runs (it has no option for it)."""
    import inspect

    from repro.models.layers import rmsnorm_apply

    return inspect.signature(rmsnorm_apply).parameters["eps"].default


def train_readings(cell, seed: int, what: list) -> list:
    """``[(kind, numbers)]``: each kind's correctness numbers against the
    reference, with the losses of its steps."""
    import jax

    from repro.kernels import registry
    from repro.runtime.train import TrainState

    drv, ref = cell.driver(), cell.reference()
    params_key = jax.random.fold_in(harness.seed_key(seed), 0)
    n = cell.traffic["check_steps"]

    def program(c, wrap=None, sets=(), sr_salt=None):
        step, state, batches, data_key, opt = drv.build(c, seed, sets)
        if wrap is not None:
            step = jax.jit(wrap(step), donate_argnums=(0,))
        if sr_salt is not None:
            state = TrainState(state.params, state.opt_state, state.step,
                               jax.random.fold_in(state.rng, sr_salt), state.ef)
        state, got = drv.program_readings(
            step, state, batches, data_key, opt, n, params_key,
            lambda k: ref.init_params(c.config, k))
        del state, step
        gc.collect()
        return got

    batches, data_key = drv.batch_source(cell, seed)
    toks = [jax.device_get(batches(data_key, j)["tokens"]) for j in range(n)]
    opt = cell.traffic["optimizer"]
    got = program(cell) if {"sound", "unchanged", "eps_program"} & set(what) else None
    reference = ref.train_readings(cell.config, params_key, toks, opt)
    rows = []
    if "sound" in what:
        rows.append(("sound", got, reference))
    if "unchanged" in what:
        rows.append(("unchanged", dict(got, change_norms={k: 0.0 for k in got["change_norms"]}),
                     reference))
    if "control" in what:
        rows.append(("control", ref.train_readings(cell.config, params_key, toks, opt,
                                                   mode="int8"), reference))
    if "dense_path" in what:
        rows.append(("dense_path", program(dataclasses.replace(
            cell, traffic=dict(cell.traffic, mode="dense"))), reference))
    if "half_batch" in what:
        rows.append(("half_batch", program(cell, wrap=_half), reference))
    if "sr_key" in what:
        rows.append(("sr_key", program(cell, sr_salt=7), reference))
    if "f32_path" in what:
        with jax.default_matmul_precision("highest"), \
                registry.kernel_policy(default="ref", ssd_scan="jnp"):
            rows.append(("f32_path", program(cell, sets=["kernels.policy=ref,ssd_scan=jnp"]),
                         reference))
    if "eps_program" in what:
        at_eps = ref.train_readings(dict(cell.config, rms_norm_eps=program_rms_eps()),
                                    params_key, toks, opt)
        rows.append(("eps_program", got, at_eps))
        rows.append(("eps_reference", at_eps, reference))
    return [(kind, dict(drv.compare_leaves(r, against), losses=r["losses"]))
            for kind, r, against in rows]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="sound,control,half_batch,unchanged")
    args = ap.parse_args(argv)
    src = harness.ROOT / "src"
    sys.path.insert(0, str(src))
    cell = harness.load_cell(args.workload)
    harness.keep_host_backend()
    devices = harness.require_chips(cell.chips)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    what = args.what.split(",")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        rows = train_readings(cell, seed, what)
        seconds = time.monotonic() - t0
        for kind, numbers in rows:
            print(json.dumps({"workload": cell.name, "seed": seed, "kind": kind,
                              "seconds": seconds, **harness.device_record(devices),
                              **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
