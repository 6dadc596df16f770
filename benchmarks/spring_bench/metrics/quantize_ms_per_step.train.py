"""Device milliseconds per training step and chip in quantization (the
operands' and products' nearest and stochastic rounding onto the fixed-
point grid, their PRNG, the weights' rounding in the optimizer): the
device time of the step's ops whose instruction the program places in
``jax.named_scope("spring_quantize")``, innermost scope first
(``step_probe.py``), over the window's steps."""

import step_probe


def read(run):
    return step_probe.scope_ms_per_step(run, "spring_quantize")
