"""Roofline share of the masked_matmul kernel (forward, dx and dw) in
training, in percent.  Work per step from ``work/<config>.py`` (every
``x @ w`` through the kernel) and ``work/masked_matmul.py`` (its calls);
a layer recomputed in the backward pass runs its forward call twice,
which the count of kernel events per step decides.  None where the
events do not match either count."""

from harness import roofline_share

PATTERN = r"^_mm_kernel(\.|$)"


def read(run):
    if run.trace is None:
        return None
    ops = run.trace.matching(PATTERN)
    c = run.counters
    if not ops or len(ops) % (c["steps"] * run.chips):
        return None
    per_step = len(ops) // (c["steps"] * run.chips)
    mm = run.work("masked_matmul")
    shapes = run.work(run.cell.config_name).matmuls(run.cell.config, c["batch"], c["seq"])
    for forward in (1, 2):
        calls = [mm.cost(*x) for m, k, n in shapes
                 for x in mm.calls_of(m, k, n, forward=forward)]
        if len(calls) == per_step:
            return roofline_share(run.trace.seconds(ops), calls * c["steps"] * run.chips,
                                  run.peaks)
    return None
