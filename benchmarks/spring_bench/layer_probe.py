"""What the program says about its MoE and MLA layers, read after the
window: the device time of their phases and the MoE row counter.

``step_probe.py`` maps instructions to its own :data:`step_probe.SCOPES`
and keeps only ``mm_tiles`` of the step it rebuilds.  This module maps
them to those scopes and the layers' (:data:`LAYER_SCOPES`), with the
same parsing (an op belongs to the innermost of :data:`SCOPES` in its
op_name), and keeps ``metrics["moe_rows"]`` (``[live, buffer, dropped]``
rows of the held experts' dispatch buffers, summed over MoE layers) of
one step of its own: the cell's step rebuilt with the ``build`` of its
``drivers/`` module from the weights of seed :data:`step_probe.SEED`
(a hit in the persistent compile cache).  It runs once per run, kept on
the run, after the window.  A program without the scopes or the counter
gives a map without them and no rows, and the readers under
``metrics/`` return None.
"""

from __future__ import annotations

import re
import sys
import traceback

import step_probe

#: The layers' phase scopes, as ``jax.named_scope`` names them.
LAYER_SCOPES = ("spring_moe_dispatch", "spring_moe_combine", "spring_mla_attention")
SCOPES = step_probe.SCOPES + LAYER_SCOPES

_SCOPE = re.compile(r"(?<![\w])(" + "|".join(SCOPES) + r")(?![\w])")


def instruction_scopes(hlo_text: str) -> dict:
    """Instruction name -> the innermost (last) of :data:`SCOPES` in its
    op_name, or None, for every instruction of the module's text."""
    out = {}
    for m in step_probe._INSTR.finditer(hlo_text):
        op_name = step_probe._OP_NAME.search(m.group(2))
        found = _SCOPE.findall(op_name.group(1)) if op_name else []
        out[m.group(1)] = found[-1] if found else None
    return out


def layer_report(run):
    """``{"module", "scopes", "moe_rows"}`` of the run's step, or None
    where the step cannot be rebuilt (the traceback goes to stderr);
    made once and kept on ``run``."""
    if not hasattr(run, "layer_report"):
        run.layer_report = _rebuild(run.cell)
    return run.layer_report


def _rebuild(cell):
    import jax

    try:
        step, state, batches, data_key, _ = cell.driver().build(cell, step_probe.SEED)
        batch = batches(data_key, 0)
        compiled = step.lower(state, batch).compile()
        text = compiled.as_text()
        _, metrics = compiled(state, batch)
        rows = metrics.get("moe_rows")
        rows = None if rows is None else [float(v) for v in jax.device_get(rows)]
    except Exception:  # a program without what is read here
        print("layer_probe: no layer report", file=sys.stderr)
        traceback.print_exc()
        return None
    return {"module": step_probe.module_name(text), "scopes": instruction_scopes(text),
            "moe_rows": rows}


def scope_seconds(trace, report) -> dict:
    """Device seconds of the step module's ops by where they belong: each
    of :data:`SCOPES`, the ``_mm_kernel`` and ``_ssd_kernel`` kernels,
    ``unscoped`` (in the map, in no scope) and ``unmapped`` (not in the
    map), summed over the trace's steps and chips."""
    kernels = ("_mm_kernel", "_ssd_kernel")
    out = dict.fromkeys(SCOPES + kernels + ("unscoped", "unmapped"), 0.0)
    for op in step_probe.step_ops(trace, report["module"]):
        if op.name not in report["scopes"]:
            where = "unmapped"
        else:
            where = report["scopes"][op.name]
            if where is None:
                kernel = op.name.split(".", 1)[0]
                where = kernel if kernel in kernels else "unscoped"
        out[where] += op.dur_ns / 1e9
    return out


def scope_ms_per_step(run, *scopes):
    """Device milliseconds per step and chip of the step's ops in any of
    ``scopes``; None without a device trace, without a report, or where
    the program names no instruction with any of them."""
    if run.trace is None or not run.trace.ops:
        return None
    report = layer_report(run)
    if report is None or not set(scopes) & set(report["scopes"].values()):
        return None
    if not step_probe.step_ops(run.trace, report["module"]):
        return None
    seconds = scope_seconds(run.trace, report)
    return 1e3 * sum(seconds[s] for s in scopes) / (run.counters["steps"] * run.chips)


def live_rows_percent(run):
    """100 * live / buffer rows of the held experts' dispatch buffers in
    the counted step; None without the counter, or where a pair was
    dropped (the buffer is then no dropless bound)."""
    report = layer_report(run)
    rows = None if report is None else report["moe_rows"]
    if not rows or rows[1] <= 0 or rows[2] > 0:
        return None
    return 100.0 * rows[0] / rows[1]
