"""The stash/restore autodiff pair (DESIGN.md §4.3).

SPRING's training story is that forward activations are written to the
monolithic-3D RRAM in binary-mask compressed form and re-read in the
backward pass.  ``stash_apply`` is the executable counterpart: a
``jax.custom_vjp`` wrapper around a block ``f(x, aux)`` whose residual is
the *compressed* input instead of the block's dense intermediates —

  forward:  y = f(x, aux);   residual = (compress(x), aux)
  backward: x = decompress(residual); grads = vjp(f, x, aux)(g)

i.e. remat-from-compressed-input: the block recomputes like ``jax.checkpoint``
but reads its input back through the compressed stash.  The modeled wire
traffic of that residual is ``nnz * value_bits + 1 bit/elem`` — the
quantity SPRING's RRAM interface moves, which the instrumentation measures
and cross-checks against the perfmodel formula.  *Device* memory under
jit's static shapes only shrinks with ``capacity < 1.0`` (the value buffer
is allocated at ``ceil(n * capacity)``); at the default capacity 1.0 the
residual is dense-length values + mask words, and what you buy is the
bit-exact restore: gradients identical to the unstashed program (dense
mode; quantized modes re-draw SR keys on the backward re-trace, the same
caveat ``jax.checkpoint`` already has with ``KeyGen``).

``checkpoint_apply`` dispatches one stash point through the per-layer
policy: "none" (XLA keeps the dense residual), "remat" (``jax.checkpoint``),
or "stash" (this wrapper).
"""

from __future__ import annotations

from functools import partial

import jax

from repro.memstash.config import MemstashConfig, STASH_POLICIES
from repro.memstash.format import compress, decompress
from repro.memstash.instrument import maybe_record


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _stashed_call(f, scfg: MemstashConfig, name: str, x, aux):
    return f(x, aux)


def _stashed_fwd(f, scfg: MemstashConfig, name: str, x, aux):
    y = f(x, aux)
    # named scopes, not host spans: under jit the pack and unpack are
    # device work, which the profiler's trace attributes by these names
    with jax.named_scope("memstash_pack"):
        sv = compress(x, capacity=scfg.capacity)
    return y, (sv, aux)


def _stashed_bwd(f, scfg: MemstashConfig, name: str, res, g):
    sv, aux = res
    with jax.named_scope("memstash_unpack"):
        x = decompress(sv)
    _, vjp = jax.vjp(f, x, aux)
    return vjp(g)


_stashed_call.defvjp(_stashed_fwd, _stashed_bwd)


def stash_apply(f, scfg: MemstashConfig, name: str, x, aux=()):
    """Run ``f(x, aux)`` storing ``x`` compressed for the backward pass.

    ``x`` is the (sparse) activation worth compressing; ``aux`` is a pytree
    of other differentiable inputs (weights, biases, small carries) kept
    dense in the residual — parameters are live in memory anyway.
    """
    maybe_record(name, x, scfg)
    return _stashed_call(f, scfg, name, x, aux)


def checkpoint_apply(f, policy: str, scfg, name: str, x, aux=()):
    """Apply one stash point under the selected checkpoint policy."""
    if policy == "none":
        return f(x, aux)
    if policy == "remat":
        return jax.checkpoint(f)(x, aux)
    if policy == "stash":
        return stash_apply(f, scfg if scfg is not None else MemstashConfig(policy="stash"),
                           name, x, aux)
    raise ValueError(f"policy {policy!r} not in {STASH_POLICIES}")
