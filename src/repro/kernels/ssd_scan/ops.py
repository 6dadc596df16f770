"""Public wrapper for the SSD chunked scan + a vectorized jnp chunked form.

``ssd_scan_jnp`` is the same chunked math as the kernel but batched over
(B, H) with plain einsums + a short lax.scan over chunks — it lowers on
any backend (the CPU dry-run path) and serves as the production fallback.

Registry entries: ``ref`` (sequential oracle), ``jnp`` (vectorized
chunked form — the only impl supporting ``return_state=True``, the
prefill -> decode cache handoff), ``interpret``, ``pallas`` (TPU).  The
kernel impls differentiate through the VJP of the chunked jnp form.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.ssd_scan.ref import ssd_scan_reference
from repro.kernels.ssd_scan.ssd_kernel import CHUNK, ssd_scan_pallas


def ssd_scan_jnp(x, dt, a, b, c, chunk: int = CHUNK, return_state: bool = False):
    """Chunked SSD, vectorized. Shapes as in ssd_scan_pallas."""
    bsz, s, h, p = x.shape
    _, _, g, n = b.shape
    group = h // g
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // chunk

    xf = x.astype(jnp.float32).reshape(bsz, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(bsz, nc, chunk, h)
    bf = b.astype(jnp.float32).reshape(bsz, nc, chunk, g, n)
    cf = c.astype(jnp.float32).reshape(bsz, nc, chunk, g, n)
    bf = jnp.repeat(bf, group, axis=3)  # (B,NC,L,H,N)
    cf = jnp.repeat(cf, group, axis=3)

    da = dtf * a[None, None, None, :]  # (B,NC,L,H)
    cum = jnp.cumsum(da, axis=2)

    # Intra-chunk dual form.  Mask the exponent (not the exp) — the upper
    # triangle has cum[t] - cum[j] > 0, which overflows exp to inf and
    # would poison the tril multiply with inf * 0 = NaN.
    scores = jnp.einsum("bclhn,bcjhn->bchlj", cf, bf)
    cum_h = jnp.moveaxis(cum, 3, 2)  # (B,NC,H,L)
    diff = cum_h[..., :, None] - cum_h[..., None, :]  # (B,NC,H,L,L)
    tril = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    w = jnp.exp(jnp.where(tril, diff, -jnp.inf))  # w[b,c,h,t,j]
    dt_h = jnp.moveaxis(dtf, 3, 2)  # (B,NC,H,L)
    s_mat = scores * w * dt_h[..., None, :]
    y_intra = jnp.einsum("bchlj,bcjhp->bclhp", s_mat, xf)

    # Chunk states and the cross-chunk scan.
    decay_end = jnp.exp(cum_h[..., -1:] - cum_h)  # (B,NC,H,L)
    chunk_state = jnp.einsum("bclhn,bchl,bclhp->bchnp", bf, decay_end * dt_h, xf)
    chunk_decay = jnp.exp(cum_h[..., -1])  # (B,NC,H)

    def scan_fn(h0, inp):
        cs, cd = inp  # (B,H,N,P), (B,H)
        h_new = h0 * cd[..., None, None] + cs
        return h_new, h0

    init = jnp.zeros((bsz, h, n, p), jnp.float32)
    h_final, h_prevs = jax.lax.scan(
        scan_fn, init, (jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # (B,NC,H,N,P) state entering each chunk

    y_inter = jnp.einsum("bclhn,bchnp->bclhp", cf, h_prevs) * jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(bsz, sp, h, p)[:, :s]
    if return_state:
        # Padded steps carry dt=0 -> decay exp(0)=1 and zero contribution,
        # so h_final is exactly the state at position S.
        return y.astype(x.dtype), h_final  # (B, H, N, P)
    return y.astype(x.dtype)


@partial(jax.jit, static_argnames=("return_state",))
def _ssd_ref(x, dt, a, b, c, *, return_state=False):
    return ssd_scan_reference(x, dt, a, b, c)


@partial(jax.jit, static_argnames=("return_state",))
def _ssd_jnp(x, dt, a, b, c, *, return_state=False):
    return ssd_scan_jnp(x, dt, a, b, c, return_state=return_state)


def _ssd_kernel_call(x, dt, a, b, c, interpret):
    s = x.shape[1]
    pad = (-s) % CHUNK
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y = ssd_scan_pallas(x, dt, a, b, c, interpret=interpret)
    return y[:, :s]


# A pallas_call has no reverse-mode rule, so training differentiates the
# kernel through the VJP of the chunked jnp form (same math, recomputed
# in the backward pass from the inputs).
def _ssd_kernel_bwd(interpret, res, g):
    with jax.named_scope("spring_ssd_scan_vjp"):
        return jax.vjp(ssd_scan_jnp, *res)[1](g)


_ssd_kernel_vjp = jax.custom_vjp(_ssd_kernel_call, nondiff_argnums=(5,))
_ssd_kernel_vjp.defvjp(
    lambda x, dt, a, b, c, interpret: (
        _ssd_kernel_call(x, dt, a, b, c, interpret), (x, dt, a, b, c)),
    _ssd_kernel_bwd,
)


@partial(jax.jit, static_argnames=("return_state", "interpret"))
def _ssd_kernel(x, dt, a, b, c, *, return_state=False, interpret=False):
    del return_state  # unsupported: the registry routes stateful calls away
    return _ssd_kernel_vjp(x, dt, a, b, c, interpret)


def _supports_state(return_state: bool = False) -> bool:
    return not return_state


def _examples() -> list:
    cases = []
    for i, (bsz, s, h, p, g, n) in enumerate(
            [(2, 320, 4, 64, 2, 32), (1, 128, 2, 32, 1, 16), (1, 96, 2, 32, 1, 16)]):
        key = jax.random.PRNGKey(i)
        x = jax.random.normal(jax.random.fold_in(key, 1), (bsz, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 2), (bsz, s, h)))
        a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 3), (h,)) * 0.5)
        b = jax.random.normal(jax.random.fold_in(key, 4), (bsz, s, g, n)) / n**0.5
        c = jax.random.normal(jax.random.fold_in(key, 5), (bsz, s, g, n)) / n**0.5
        cases.append(((x, dt, a, b, c), {}))
    return cases


registry.register_op("ssd_scan", oracle="ref", examples=_examples,
                     compare={"kind": "rel", "tol": 1e-4})
registry.register_impl("ssd_scan", "ref", supports=_supports_state)(_ssd_ref)
registry.register_impl("ssd_scan", "jnp", priority=20)(_ssd_jnp)
registry.register_impl("ssd_scan", "interpret", selectable=False,
                       supports=_supports_state)(
    partial(_ssd_kernel, interpret=True))
registry.register_impl("ssd_scan", "pallas", priority=30,
                       available=registry.on_tpu, supports=_supports_state)(
    partial(_ssd_kernel, interpret=False))


def ssd_scan(x, dt, a, b, c, impl: str | None = None, return_state: bool = False):
    """SSD scan through the kernel registry.

    ``return_state=True`` (the prefill -> decode cache handoff) also
    returns the final (B,H,N,P) state; only the ``jnp`` implementation
    supports it — pinning any other impl raises a ValueError naming the
    impl, and auto-selection routes around it.
    """
    kimpl = registry.resolve("ssd_scan", impl, return_state=return_state)
    return kimpl.fn(x, dt, a, b, c, return_state=return_state)
