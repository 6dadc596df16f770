"""Device milliseconds per training step and chip in the optimizer
(gradient clipping, AdamW, the weights' update): the device time of the
step's ops whose instruction the program places in
``jax.named_scope("spring_optimizer")``, innermost scope first
(``step_probe.py``), over the window's steps."""

import step_probe


def read(run):
    return step_probe.scope_ms_per_step(run, "spring_optimizer")
