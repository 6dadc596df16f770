"""What the program says about its own train step, read after the window.

The profiler names a device op only by its HLO instruction
(``fusion.323``, ``_mm_kernel.92``).  The program names its phases with
``jax.named_scope``; the names reach the optimized HLO of the step as
each instruction's ``metadata={op_name="jit(...)/.../<scope>/..."}``.
:func:`instruction_scopes` maps every instruction of a compiled module to
the innermost phase of :data:`SCOPES` in its op_name (or to None).

:func:`step_report` rebuilds the cell's step with the ``build`` of its
``drivers/`` module (the same function at the same shapes, so the
compile is a hit in the persistent cache and the instruction names are
those of the window's executable), takes that map from its optimized
HLO, and runs the
step once, from the weights of seed :data:`SEED`, for masked_matmul's
tile counter (``metrics["mm_tiles"]``: the forward, dx and dw calls'
issued and total grid steps).  It runs once per run, kept on the run
that the readers share, after the window, when the window's state has
been freed.  A program that has no such scopes or counter gives a map
without them and no counts, and the readers under ``metrics/`` return
None.
"""

from __future__ import annotations

import re
import sys
import traceback

import tracing

#: The program's phase scopes, as ``jax.named_scope`` names them.
SCOPES = ("spring_quantize", "spring_mm_prep", "spring_ssd_scan_vjp", "spring_optimizer")
#: Seed of the weights and the batch of the counted step.
SEED = 0

_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z_][\w.\-]*)\s+=\s+(.*)$", re.M)
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_SCOPE = re.compile(r"(?<![\w])(" + "|".join(SCOPES) + r")(?![\w])")


def module_name(hlo_text: str) -> str:
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else ""


def instruction_scopes(hlo_text: str) -> dict:
    """Instruction name -> the innermost (last) of :data:`SCOPES` in its
    op_name, or None, for every instruction of the module's text."""
    out = {}
    for m in _INSTR.finditer(hlo_text):
        op_name = _OP_NAME.search(m.group(2))
        found = _SCOPE.findall(op_name.group(1)) if op_name else []
        out[m.group(1)] = found[-1] if found else None
    return out


def step_report(run):
    """``{"module", "scopes", "mm_tiles"}`` of the run's step, or None
    where the step cannot be rebuilt (the traceback goes to stderr);
    made once and kept on ``run``."""
    if not hasattr(run, "step_report"):
        run.step_report = _rebuild(run.cell)
    return run.step_report


def _rebuild(cell):
    import jax

    try:
        step, state, batches, data_key, _ = cell.driver().build(cell, SEED)
        batch = batches(data_key, 0)
        compiled = step.lower(state, batch).compile()
        text = compiled.as_text()
        _, metrics = compiled(state, batch)
        tiles = metrics.get("mm_tiles")
        tiles = None if tiles is None else [float(v) for v in jax.device_get(tiles)]
    except Exception:  # a program without what is read here
        print("step_probe: no step report", file=sys.stderr)
        traceback.print_exc()
        return None
    return {"module": module_name(text), "scopes": instruction_scopes(text),
            "mm_tiles": tiles}


def step_ops(trace, module: str) -> list:
    """The leaf ops of ``module`` in the trace (control flow, whose events
    span the ops of their bodies, left out)."""
    return [op for op in trace.ops
            if op.module == module and not tracing.CONTAINER.match(op.name)]


def scope_seconds(trace, report) -> dict:
    """Device seconds of the step module's ops by where they belong: each
    scope of :data:`SCOPES`, the ``_mm_kernel`` and ``_ssd_kernel``
    kernels, ``unscoped`` (in the map, in no scope) and ``unmapped`` (not
    in the map), summed over the trace's steps and chips."""
    out = dict.fromkeys(SCOPES + ("_mm_kernel", "_ssd_kernel", "unscoped", "unmapped"), 0.0)
    for op in step_ops(trace, report["module"]):
        if op.name not in report["scopes"]:
            where = "unmapped"
        else:
            where = report["scopes"][op.name]
            if where is None:
                kernel = op.name.split(".", 1)[0]
                where = kernel if kernel in ("_mm_kernel", "_ssd_kernel") else "unscoped"
        out[where] += op.dur_ns / 1e9
    return out


def scope_ms_per_step(run, scope: str):
    """Device milliseconds per step and chip of the step's ops in
    ``scope``; None without a device trace, without a step report, or
    where the program names no instruction with that scope."""
    if run.trace is None or not run.trace.ops:
        return None
    report = step_report(run)
    if report is None or scope not in report["scopes"].values():
        return None
    if not step_ops(run.trace, report["module"]):
        return None
    seconds = scope_seconds(run.trace, report)[scope]
    return 1e3 * seconds / (run.counters["steps"] * run.chips)


def tile_skip_percent(run):
    """Percent of masked_matmul's grid steps skipped over the forward, dx
    and dw calls of the counted step; None where the program counts none."""
    report = step_report(run)
    tiles = None if report is None else report["mm_tiles"]
    if not tiles or tiles[1] + tiles[3] + tiles[5] <= 0:
        return None
    issued = tiles[0] + tiles[2] + tiles[4]
    return 100.0 * (1.0 - issued / (tiles[1] + tiles[3] + tiles[5]))
