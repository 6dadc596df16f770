"""Device milliseconds per training step and chip in masked_matmul's
preparation (padding to MXU tiles, the occupancy tables, the tile
counter) around its kernel: the device time of the step's ops whose
instruction the program places in ``jax.named_scope("spring_mm_prep")``,
innermost scope first (``step_probe.py``), over the window's steps."""

import step_probe


def read(run):
    return step_probe.scope_ms_per_step(run, "spring_mm_prep")
