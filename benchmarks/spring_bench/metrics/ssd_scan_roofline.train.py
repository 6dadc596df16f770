"""Roofline share of the forward ssd_scan kernel in training, in percent
(its backward runs the chunked jnp form, which has no kernel name).  Work
from ``work/ssd_scan.py`` at the activations' dtype (bf16 in dense mode,
float32 in the quantized modes); a layer recomputed in the backward pass
runs the kernel twice, which the count of kernel events per step
decides."""

from harness import roofline_share

PATTERN = r"^_ssd_kernel(\.|$)"


def read(run):
    if run.trace is None:
        return None
    ops = run.trace.matching(PATTERN)
    c = run.counters
    if not ops or len(ops) % (c["steps"] * run.chips):
        return None
    per_step = len(ops) // (c["steps"] * run.chips)
    nbytes = 2 if run.cell.traffic["mode"] == "dense" else 4
    scans = run.work(run.cell.config_name).ssd_scans(run.cell.config, c["batch"], c["seq"])
    ssd = run.work("ssd_scan")
    for times in (1, 2):
        if len(scans) * times == per_step:
            calls = [ssd.cost(*s, x_bytes=nbytes, bc_bytes=nbytes) for s in scans] * times
            return roofline_share(run.trace.seconds(ops), calls * c["steps"] * run.chips,
                                  run.peaks)
    return None
