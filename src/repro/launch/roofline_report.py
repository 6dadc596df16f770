"""Aggregate dry-run JSONs into the EXPERIMENTS.md §Dry-run / §Roofline
tables.

  PYTHONPATH=src python -m repro.launch.roofline_report results/dryrun
"""

from __future__ import annotations

import glob
import json
import os
import sys


def load_all(d: str) -> list[dict]:
    out = []
    for f in sorted(glob.glob(os.path.join(d, "*.json"))):
        try:
            out.append(json.load(open(f)))
        except Exception:
            pass
    return out


def fmt_bytes(b: float) -> str:
    return f"{b/1e9:.2f}"


def dryrun_table(rows: list[dict]) -> str:
    lines = [
        "| arch | shape | mesh | chips | peak GB/chip | HLO GFLOP/chip | coll GB/chip (AG/AR/RS/A2A/CP) | compile s |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["status"] == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | - | skipped | - | {r['reason'][:50]} | - |")
            continue
        c = r["collectives"]
        coll = "/".join(
            f"{c.get(k,0)/1e9:.1f}" for k in
            ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute"))
        rl = r["roofline"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['n_chips']} "
            f"| {r['memory']['peak_bytes_per_chip_est']/1e9:.2f} "
            f"| {rl['hlo_flops_per_chip']/1e9:.0f} "
            f"| {coll} "
            f"| {r['compile_s']}+{r.get('cost_compile_s') or 0} |")
    return "\n".join(lines)


def roofline_table(rows: list[dict], mesh: str = "single") -> str:
    lines = [
        "| arch | shape | compute s | memory s | collective s | dominant | MODEL_FLOPS | useful ratio |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["status"] != "ok" or r["mesh"] != mesh:
            continue
        t = r["roofline"]
        lines.append(
            f"| {r['arch']} | {r['shape']} "
            f"| {t['compute_s']:.4f} | {t['memory_s']:.4f} | {t['collective_s']:.4f} "
            f"| {t['dominant'].replace('_s','')} "
            f"| {t.get('model_flops',0):.3e} | {t.get('useful_flops_ratio',0):.2f} |")
    return "\n".join(lines)


def memstash_table(results: list[dict]) -> str:
    """Render ``repro.memstash.report`` JSONs: measured stash traffic per
    model vs the analytical binary-mask formula (bits/elem = 20*d + 1)."""
    lines = [
        "| model | stash points | mean density | dense fp32 MB | wire MB | ratio | wire/formula |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in results:
        s = r.get("summary", {})
        if not s.get("stash_points"):
            continue
        lines.append(
            f"| {r['model']} | {s['stash_points']} | {s['mean_density']:.3f} "
            f"| {s['dense_fp32_bytes']/1e6:.2f} | {s['wire_bytes']/1e6:.2f} "
            f"| {s['compression_vs_fp32']:.2f}x | {s['wire_vs_formula']:.4f} |")
    return "\n".join(lines)


def kernel_table(rows: list[dict]) -> str:
    """Render the per-cell kernel backend attribution (dry-run
    ``kernel_impls`` / ``kernel_dispatch``, emitted since the dispatch
    registry landed; older JSONs without the fields are skipped)."""
    lines = [
        "| arch | shape | policy | resolved (op=impl) | dispatches |",
        "|---|---|---|---|---|",
    ]
    any_row = False
    for r in rows:
        impls = r.get("kernel_impls")
        if r.get("status") != "ok" or not impls:
            continue
        any_row = True
        resolved = " ".join(f"{op}={name}" for op, name in sorted(impls.items())
                            if not str(name).startswith("error"))
        disp = r.get("kernel_dispatch") or {}
        dispatched = " ".join(
            f"{op}:{name}x{n}" for op, by in sorted(disp.items())
            for name, n in sorted(by.items())) or "-"
        lines.append(f"| {r['arch']} | {r['shape']} "
                     f"| {r.get('kernel_policy', 'auto')} | {resolved} | {dispatched} |")
    return "\n".join(lines) if any_row else ""


def backward_sparsity_table(rows: list[dict]) -> str:
    """Render per-cell backward tile-skip probes (dry-run ``sparsity_probe``
    emitted for quant_sparse train cells since the sparsity-aware backward
    landed; older JSONs without the field are skipped).  Forward and
    backward skip fractions are attributed separately — the backward
    columns are what the custom_vjp dx/dw kernels measured."""
    lines = [
        "| arch | shape | bwd policy | probe density | fwd skip | dX skip | dW skip |",
        "|---|---|---|---|---|---|---|",
    ]
    any_row = False
    for r in rows:
        p = r.get("sparsity_probe")
        if r.get("status") != "ok" or not p:
            continue
        any_row = True

        def f(v):
            return "-" if v is None else f"{v:.3f}"

        lines.append(
            f"| {r['arch']} | {r['shape']} | {r.get('backward_sparsity', 'auto')} "
            f"| {p['density']:.2f} | {f(p['forward_tile_skip'])} "
            f"| {f(p['backward_tile_skip_dx'])} | {f(p['backward_tile_skip_dw'])} |")
    return "\n".join(lines) if any_row else ""


def kv_cache_table(rows: list[dict]) -> str:
    """Render per-cell serving KV-compression probes (dry-run ``kv_probe``
    emitted for quant_sparse decode cells since spring-serve landed;
    older JSONs without the field are skipped)."""
    lines = [
        "| arch | shape | impl | density | wire KB | vs fp32 | wire/formula |",
        "|---|---|---|---|---|---|---|",
    ]
    any_row = False
    for r in rows:
        p = r.get("kv_probe")
        if r.get("status") != "ok" or not p:
            continue
        any_row = True
        lines.append(
            f"| {r['arch']} | {r['shape']} | {p.get('impl', '-')} "
            f"| {p['density']:.2f} | {p['wire_bytes']/1e3:.1f} "
            f"| {p['compression_vs_fp32']:.2f}x | {p['wire_vs_formula']:.4f} |")
    return "\n".join(lines) if any_row else ""


def collectives_table(rows: list[dict]) -> str:
    """spring-mesh packed-collective accounting per dry-run cell: the
    simulated wire bytes of one packed all-gather at the cell's probe
    density, the reduction vs a dense fp32 collective, the ``20·d + 1``
    formula cross-check, and any divisibility fallbacks the sharding
    rules hit (``collective_probe`` / ``mesh_fallbacks`` fields, emitted
    since spring-mesh landed; older JSONs are skipped)."""
    lines = [
        "| arch | shape | mesh | world | density | wire KB | vs fp32 | wire/formula | exact | fallbacks |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    any_row = False
    for r in rows:
        p = r.get("collective_probe")
        fb = r.get("mesh_fallbacks") or {}
        if r.get("status") != "ok" or (not p and not fb):
            continue
        any_row = True
        fbs = " ".join(f"{k}x{int(v)}" for k, v in sorted(fb.items())) or "-"
        if p:
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | {p['world']} "
                f"| {p['density']:.2f} | {p['wire_bytes']/1e3:.1f} "
                f"| {p['compression_vs_fp32']:.2f}x | {p['wire_vs_formula']:.4f} "
                f"| {'yes' if p.get('exact') else 'NO'} | {fbs} |")
        else:
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} "
                f"| - | - | - | - | - | - | {fbs} |")
    return "\n".join(lines) if any_row else ""


def serving_table(results: list[dict]) -> str:
    """Render ``repro.launch.serve --json`` engine sessions: per-request
    latency percentiles, throughput, slot occupancy and measured KV
    wire traffic of the compressed pool."""
    lines = [
        "| mode | slots | requests | tok/s | occupancy | p50 ms | p100 ms | KV wire/step | vs fp32 | spec |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    any_row = False
    for r in results:
        reqs = r.get("per_request")
        if not r.get("engine") or not reqs:
            continue
        any_row = True
        lat = sorted(q["latency_s"] for q in reqs)
        lines.append(
            f"| {r.get('mode', '-')} | {r.get('slots', '-')} | {len(reqs)} "
            f"| {r['tokens_per_s']:.1f} | {r['mean_occupancy']:.2f} "
            f"| {lat[len(lat)//2]*1e3:.0f} | {lat[-1]*1e3:.0f} "
            f"| {r['kv_mean_wire_bytes']/1e3:.1f}KB "
            f"| {r['kv_traffic_reduction_vs_fp32']:.2f}x "
            f"| {r.get('spec_hash', '-')[:10]} |")
    if not any_row:
        return ""
    out = "\n".join(lines)
    at = latency_attribution_table(results)
    if at:
        out += f"\n\n{at}"
    pt = paging_table(results)
    if pt:
        out += f"\n\n{pt}"
    return out


def paging_table(results: list[dict]) -> str:
    """spring-pages sessions per ``serve --json``: the paged COW pool's
    physical budget, peak residency, prefix sharing and spill traffic
    (``summary()["paging"]``; non-paged sessions are skipped)."""
    lines = [
        "| mode | pages | overcommit | peak resident | prefix hits | cow | spills/resumes | peak util | spec |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    any_row = False
    for r in results:
        p = r.get("paging")
        if not r.get("engine") or not p:
            continue
        any_row = True
        lines.append(
            f"| {r.get('mode', '-')} "
            f"| {p['num_pages']}x{p['page_tokens']}tok "
            f"| x{p['overcommit']:.1f} ({p['logical_frames']} logical) "
            f"| {p['peak_active']} | {p['prefix_hits']} | {p['cow_copies']} "
            f"| {p['spills']}/{p['resumes']} "
            f"| {p['peak_page_utilization']:.2f} "
            f"| {r.get('spec_hash', '-')[:10]} |")
    return "\n".join(lines) if any_row else ""


def latency_attribution_table(results: list[dict]) -> str:
    """spring-trace latency attribution per engine session: where a
    request's wall-clock went (queue-wait vs TTFT vs steady-state token
    cadence) plus scheduler tick utilization — from the engine's
    streaming quantile sketches (``summary()["latency"]``)."""
    lines = [
        "| mode | queue p50/p95 ms | ttft p50/p95 ms | token p50/p95/p99 ms | ticks | tick util | spec |",
        "|---|---|---|---|---|---|---|",
    ]
    any_row = False
    for r in results:
        la = r.get("latency")
        if not r.get("engine") or not la:
            continue
        any_row = True
        q, t, tok = la["queue_s"], la["ttft_s"], la["token_s"]
        lines.append(
            f"| {r.get('mode', '-')} "
            f"| {q['p50']*1e3:.0f}/{q['p95']*1e3:.0f} "
            f"| {t['p50']*1e3:.0f}/{t['p95']*1e3:.0f} "
            f"| {tok['p50']*1e3:.1f}/{tok['p95']*1e3:.1f}/{tok['p99']*1e3:.1f} "
            f"| {la['ticks']} | {la['tick_utilization']:.2f} "
            f"| {r.get('spec_hash', '-')[:10]} |")
    return "\n".join(lines) if any_row else ""


def pick_hillclimb(rows: list[dict]) -> list[str]:
    ok = [r for r in rows if r["status"] == "ok" and r["mesh"] == "single"]
    notes = []
    if not ok:
        return notes
    coll = max(ok, key=lambda r: r["roofline"]["collective_s"])
    notes.append(f"most-collective-bound: {coll['arch']} x {coll['shape']} "
                 f"(coll {coll['roofline']['collective_s']:.3f}s)")
    return notes


def main():
    d = sys.argv[1] if len(sys.argv) > 1 else "results/dryrun"
    rows = load_all(d)
    print("## Dry-run table\n")
    print(dryrun_table(rows))
    print("\n## Roofline (single-pod)\n")
    print(roofline_table(rows, "single"))
    print("\n## Roofline (multi-pod)\n")
    print(roofline_table(rows, "multi"))
    kt = kernel_table(rows)
    if kt:
        print("\n## Kernel dispatch (registry-resolved backends)\n")
        print(kt)
    bt = backward_sparsity_table(rows)
    if bt:
        print("\n## Backward sparsity (measured tile-skip, fwd vs dX/dW)\n")
        print(bt)
    kv = kv_cache_table(rows)
    if kv:
        print("\n## Serving KV cache (measured compression probes)\n")
        print(kv)
    ct = collectives_table(rows)
    if ct:
        print("\n## Packed collectives (spring-mesh wire accounting)\n")
        print(ct)
    print("\n## Hillclimb candidates\n")
    for n in pick_hillclimb(rows):
        print("-", n)
    # memstash accounting lives next to the dry-run dir (results/memstash)
    ms_dir = os.path.join(os.path.dirname(os.path.normpath(d)) or ".", "memstash")
    ms_rows = load_all(ms_dir)
    if ms_rows:
        print("\n## Memstash (compressed activation stash)\n")
        print(memstash_table(ms_rows))
    # engine sessions live next to the dry-run dir (results/serving),
    # written by `repro.launch.serve --json`
    sv_dir = os.path.join(os.path.dirname(os.path.normpath(d)) or ".", "serving")
    sv_rows = load_all(sv_dir)
    st = serving_table(sv_rows)
    if st:
        print("\n## Serving engine sessions\n")
        print(st)


if __name__ == "__main__":
    main()
