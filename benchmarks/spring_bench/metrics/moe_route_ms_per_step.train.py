"""Device milliseconds per training step and chip in the MoE layers'
routing: the router, top-k, the share mask and the dispatch into the held
experts' buffers (``jax.named_scope("spring_moe_dispatch")``) and the
gate-weighted combine back to tokens (``"spring_moe_combine"``), forward
and backward, innermost scope first (``layer_probe.py``), over the
window's steps.  The experts' matmuls are ``_mm_kernel``."""

import layer_probe


def read(run):
    return layer_probe.scope_ms_per_step(run, "spring_moe_dispatch", "spring_moe_combine")
