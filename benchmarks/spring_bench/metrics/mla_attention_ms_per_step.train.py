"""Device milliseconds per training step and chip in multi-head latent
attention between its projections: the kv norm, the latent up-
projections, YaRN rope, scores, softmax and values, forward and backward
(``jax.named_scope("spring_mla_attention")``, innermost scope first,
``layer_probe.py``), over the window's steps.  The q, kv-down, rope-key
and output projections are ``_mm_kernel``."""

import layer_probe


def read(run):
    return layer_probe.scope_ms_per_step(run, "spring_mla_attention")
