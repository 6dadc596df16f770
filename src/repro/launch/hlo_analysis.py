"""Roofline-term extraction from compiled dry-run artifacts.

``cost_analysis()`` supplies HLO FLOPs and bytes; collective bytes are
NOT in cost_analysis, so we parse the optimized HLO text and sum the
result-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (assignment §Roofline).  Hardware
constants: TPU v5e-class — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI.  The terms are estimates from a compile, not device
measurements, so no utilization or fraction of peak is derived here.
"""

from __future__ import annotations

import re
from typing import Any

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    """Sum bytes over every 'dtype[dims]' group in a (possibly tuple) shape."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str, bf16_correct: bool = False) -> dict[str, int]:
    """Per-collective-kind result bytes summed over the module.

    Convention: the *result* shape approximates payload per chip (for
    all-gather that is the received bytes; for reduce-scatter the operand
    is larger but the wire traffic matches the scattered result x (P-1)).
    fusion-internal collectives don't exist post-SPMD, so line scanning
    is sound.

    ``bf16_correct``: the CPU backend legalizes bf16 dots to f32 (convert-
    wrapped operands), so activation-path collectives carry f32 payloads
    that are bf16 on the TPU target — count f32 payloads at 2 bytes/elem.
    Raw totals are reported alongside as ``*_raw``.
    """
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    raw_total = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)", s)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        kind = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-start") or op == c + "-done":
                kind = c
                break
        if kind is None:
            continue
        if op.endswith("-done"):
            continue  # counted at -start
        b = _shape_bytes(shape_str)
        raw_total += b
        if bf16_correct:
            b = _shape_bytes(shape_str.replace("f32[", "bf16["))
        out[kind] += b
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["total_raw_f32"] = raw_total
    return out


_TRAFFIC_OPS = ("dot", "convolution", "gather", "scatter", "dynamic-update-slice",
                "dynamic-slice", "copy", "reduce-window", "sort")


def fusion_adjusted_bytes(hlo_text: str, bf16_correct: bool = False) -> dict[str, float]:
    """TPU-realistic HBM traffic estimate from CPU-compiled HLO.

    The CPU pipeline leaves elementwise chains unfused, so cost_analysis
    "bytes accessed" counts every intermediate (observed ~10x inflation:
    convert/add/broadcast dominate).  On the TPU target those chains fuse
    into their producers/consumers; the HBM traffic that remains is
    (a) matmul/conv operands + results, (b) data-movement ops
    (gather/scatter/slice-update/copy/sort), (c) collective payloads,
    (d) entry parameters/outputs.  We reconstruct (a)-(b) with a
    symbol-table walk so *operand* shapes resolve, and report this as the
    memory-roofline numerator next to the raw number.
    """
    symbols: dict[str, str] = {}
    traffic = 0.0
    params_bytes = 0.0
    line_re = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+([\w\-]+)")
    for raw in hlo_text.splitlines():
        m = line_re.match(raw)
        if not m:
            continue
        name, shape_str, op = m.groups()
        symbols[name.lstrip("%")] = shape_str
        if op == "parameter":
            continue
        if op in _TRAFFIC_OPS:
            eff = shape_str.replace("f32[", "bf16[") if bf16_correct else shape_str
            b = _shape_bytes(eff)
            # operand bytes via the symbol table (CPU HLO uses bare %refs;
            # only the op's own parens, not attribute/metadata parens)
            op_call = raw.find("(")
            args = raw[op_call + 1 : raw.find(")", op_call)]
            for ref in re.findall(r"%([\w.\-]+)", args):
                if ref in symbols:
                    sh = symbols[ref]
                    b += _shape_bytes(sh.replace("f32[", "bf16[") if bf16_correct else sh)
            traffic += b
    return {"fusion_adjusted_bytes": traffic}


def roofline_terms(
    cost: dict[str, Any],
    coll_bytes: int,
    n_chips: int,
    model_flops: float | None = None,
    adjusted_bytes: float | None = None,
) -> dict[str, float]:
    """The three roofline terms, in seconds.

    XLA's cost_analysis and post-SPMD HLO shapes are PER-CHIP, so the
    assignment formulas `global / (chips x rate)` reduce to
    `per_chip / rate`; global totals are recorded alongside
    (= per-chip x chips, exact for the homogeneous SPMD programs here).
    """
    flops_pc = float(cost.get("flops", 0.0))
    bytes_pc = float(cost.get("bytes accessed", 0.0))
    compute_s = flops_pc / PEAK_FLOPS
    memory_s_raw = bytes_pc / HBM_BW
    # dominant-term decisions use the fusion-adjusted traffic when given
    # (raw CPU-backend bytes overcount unfused elementwise chains ~10x)
    mem_bytes = adjusted_bytes if adjusted_bytes is not None else bytes_pc
    memory_s = mem_bytes / HBM_BW
    collective_s = coll_bytes / ICI_BW
    terms = {
        "hlo_flops_per_chip": flops_pc,
        "hlo_flops_global": flops_pc * n_chips,
        "hlo_bytes_per_chip_raw": bytes_pc,
        "hlo_bytes_per_chip_fusion_adjusted": float(mem_bytes),
        "collective_bytes_per_chip": float(coll_bytes),
        "compute_s": compute_s,
        "memory_s": memory_s,
        "memory_s_raw": memory_s_raw,
        "collective_s": collective_s,
    }
    dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    terms["dominant"] = dom
    if model_flops is not None:
        terms["model_flops"] = float(model_flops)
        g = flops_pc * n_chips
        terms["useful_flops_ratio"] = (model_flops / g) if g else 0.0
    return terms


def memory_summary(mem_analysis) -> dict[str, float]:
    keys = [
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ]
    out = {}
    for k in keys:
        v = getattr(mem_analysis, k, None)
        if v is not None:
            out[k] = float(v)
    out["peak_bytes_per_chip_est"] = (
        out.get("argument_size_in_bytes", 0.0)
        + out.get("output_size_in_bytes", 0.0)
        + out.get("temp_size_in_bytes", 0.0)
        - out.get("alias_size_in_bytes", 0.0)
    )
    return out
