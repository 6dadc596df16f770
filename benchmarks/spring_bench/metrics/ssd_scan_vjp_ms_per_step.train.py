"""Device milliseconds per training step and chip in the ssd_scan kernel's
backward (the VJP of the chunked jnp form): the device time of the
step's ops whose instruction the program places in
``jax.named_scope("spring_ssd_scan_vjp")``, innermost scope first
(``step_probe.py``), over the window's steps."""

import step_probe


def read(run):
    return step_probe.scope_ms_per_step(run, "spring_ssd_scan_vjp")
