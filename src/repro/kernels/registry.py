"""Unified kernel-dispatch registry: one backend policy for every SPRING op.

Every Pallas op family (``masked_matmul``, ``mask_pack`` / ``mask_unpack`` /
``dangling_filter``, ``stochastic_round``, ``flash_attention``,
``ssd_scan``) registers its implementations here with capability
predicates, and every public wrapper resolves through :func:`resolve`
instead of a hand-rolled ``if impl == "auto"`` ladder.  The registry is
the single place where

  * backend selection lives — ``auto`` picks the highest-priority
    implementation whose availability predicate passes on the current
    backend (Pallas on TPU, the best vectorized lowering elsewhere);
  * whole-program pinning lives — a :class:`KernelPolicy` (global default
    + per-op overrides) threaded through ``SpringConfig`` and settable
    ambiently via the ``SPRING_KERNEL_IMPL`` env var or the
    :func:`kernel_policy` context manager;
  * per-op dispatch counters and instrumentation metrics live (tile-skip
    fraction from ``masked_matmul``, wire bytes from ``mask_compress``),
    feeding ``perfmodel/spring_model.py`` and ``launch/roofline_report``;
  * the parity contract lives — each op registers example inputs and a
    comparison spec, from which ``tests/test_kernel_registry.py`` and
    ``benchmarks/bench_kernels.py --smoke`` generate oracle-vs-impl
    cross-checks for every registered (op, impl) pair runnable on the
    current backend.  A kernel that is not registered cannot be exercised
    by CI's kernel-parity job, and the registration-completeness test
    fails if a ``kernels/<op>/ops.py`` package registers nothing.

Resolution precedence (highest first):

  1. an explicit concrete ``impl=`` argument at the call site (this is
     how ``SpringConfig.kernels`` reaches the ops: model code passes
     ``impl=ctx.kernel_impl(op)``);
  2. a per-op override in the active policy — strict: unknown or
     unavailable implementations raise;
  3. the active policy's global default — soft: ops that do not register
     that implementation fall back to ``auto`` (so
     ``SPRING_KERNEL_IMPL=jnp`` pins what it can and leaves the rest
     sensible), but an *unavailable* registered implementation still
     raises (asking for ``pallas`` on CPU is an error, not a shrug);
  4. ``auto`` — highest-priority available *selectable* implementation
     that supports the call's capability kwargs (``interpret`` is
     registered everywhere but never auto-selected: it is a test mode).
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import os
import threading
from typing import Any, Callable, Optional

import jax

ENV_VAR = "SPRING_KERNEL_IMPL"

#: The closed set of implementation names an op may register.
IMPL_NAMES = ("ref", "jnp", "interpret", "pallas")

#: ops.py modules that self-register on import (lazy to avoid cycles).
_OP_MODULES = (
    "repro.kernels.masked_matmul.ops",
    "repro.kernels.masked_matmul.backward",
    "repro.kernels.mask_compress.ops",
    "repro.kernels.kv_cache.ops",
    "repro.kernels.stochastic_round.ops",
    "repro.kernels.flash_attention.ops",
    "repro.kernels.ssd_scan.ops",
    "repro.dist.collectives",
)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _true() -> bool:
    return True


# ---------------------------------------------------------------------------
# Registration records.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """One registered implementation of one op."""

    op: str
    name: str  # one of IMPL_NAMES
    fn: Callable
    #: auto picks the highest-priority available+selectable impl.
    priority: int = 0
    #: can this impl execute on the current backend at all?
    available: Callable[[], bool] = _true
    #: eligible for auto-selection (interpret mode is explicit-only).
    selectable: bool = True
    #: include in the generated parity suite (aliases opt out).
    parity: bool = True
    #: per-call capability predicate over capability kwargs
    #: (e.g. ``return_state`` for ssd_scan); None = supports everything.
    supports: Optional[Callable[..., bool]] = None

    def supports_call(self, **caps: Any) -> bool:
        return self.supports is None or bool(self.supports(**caps))


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """Parity/oracle contract for one op."""

    name: str
    oracle: str = "ref"
    #: zero-arg callable -> list of (args, kwargs) example invocations.
    examples: Optional[Callable[[], list]] = None
    #: comparison spec for the parity harness:
    #:   {"kind": "exact"} | {"kind": "allclose", "atol": a, "rtol": r}
    #:   | {"kind": "rel", "tol": t}  (max-abs error over max-abs oracle)
    compare: tuple = (("kind", "exact"),)

    def compare_spec(self) -> dict:
        return dict(self.compare)


_OPS: dict[str, OpSpec] = {}
_IMPLS: dict[str, dict[str, KernelImpl]] = {}
_IMPORTED = False


def ensure_registered() -> None:
    """Import every kernels/*/ops.py so their registrations run."""
    global _IMPORTED
    if _IMPORTED:
        return
    for mod in _OP_MODULES:
        importlib.import_module(mod)
    _IMPORTED = True


def register_op(
    name: str,
    *,
    oracle: str = "ref",
    examples: Optional[Callable[[], list]] = None,
    compare: Optional[dict] = None,
) -> None:
    cmp = tuple(sorted((compare or {"kind": "exact"}).items()))
    _OPS[name] = OpSpec(name=name, oracle=oracle, examples=examples, compare=cmp)
    _IMPLS.setdefault(name, {})


def register_impl(
    op: str,
    name: str,
    *,
    priority: int = 0,
    available: Callable[[], bool] = _true,
    selectable: bool = True,
    parity: bool = True,
    supports: Optional[Callable[..., bool]] = None,
) -> Callable[[Callable], Callable]:
    """Decorator: register ``fn`` as the ``name`` implementation of ``op``."""
    if name not in IMPL_NAMES:
        raise ValueError(f"impl name {name!r} not in {IMPL_NAMES}")
    if op not in _OPS:
        raise ValueError(f"register_op({op!r}) must run before register_impl")

    def deco(fn: Callable) -> Callable:
        _IMPLS[op][name] = KernelImpl(
            op=op, name=name, fn=fn, priority=priority, available=available,
            selectable=selectable, parity=parity, supports=supports,
        )
        return fn

    return deco


def ops() -> list[str]:
    ensure_registered()
    return sorted(_OPS)


def op_spec(op: str) -> OpSpec:
    ensure_registered()
    return _OPS[op]


def impls(op: str) -> dict[str, KernelImpl]:
    ensure_registered()
    if op not in _IMPLS:
        raise KeyError(f"unknown kernel op {op!r}; registered: {sorted(_OPS)}")
    return dict(_IMPLS[op])


# ---------------------------------------------------------------------------
# Policy: global default + per-op overrides.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Immutable (hashable) backend policy threaded through SpringConfig.

    ``default`` applies to every op that registers it; ``overrides`` pins
    specific ops and is strict.  ``"auto"`` defers to capability-based
    selection.
    """

    default: str = "auto"
    overrides: tuple = ()  # tuple[(op, impl), ...] — hashable for jit

    def __post_init__(self):
        names = ("auto",) + IMPL_NAMES
        if self.default not in names:
            raise ValueError(
                f"unknown kernel impl {self.default!r}; choose from {names}")
        for op, name in self.overrides:
            if name not in names:
                raise ValueError(
                    f"unknown kernel impl {name!r} for op {op!r}; "
                    f"choose from {names}")
        if self.overrides:  # a misspelled op would silently pin nothing
            ensure_registered()
            for op, _ in self.overrides:
                if op not in _OPS:
                    raise ValueError(
                        f"unknown kernel op {op!r} in policy overrides; "
                        f"registered ops: {sorted(_OPS)}")

    def impl_for(self, op: str) -> str:
        return dict(self.overrides).get(op, self.default)

    @property
    def is_auto(self) -> bool:
        return self.default == "auto" and not self.overrides

    @classmethod
    def parse(cls, spec: str) -> "KernelPolicy":
        """Parse ``"ref"`` / ``"ssd_scan=jnp"`` / ``"ref,ssd_scan=jnp"``.

        Bare tokens set the global default; ``op=impl`` tokens add per-op
        overrides.  Op names are validated against the registry.
        """
        default = "auto"
        overrides: list[tuple[str, str]] = []
        for token in (t.strip() for t in (spec or "").split(",")):
            if not token:
                continue
            if "=" in token:
                op, _, name = token.partition("=")
                op, name = op.strip(), name.strip()
                ensure_registered()
                if op not in _OPS:
                    raise ValueError(
                        f"unknown kernel op {op!r} in policy spec {spec!r}; "
                        f"registered ops: {sorted(_OPS)}")
                overrides.append((op, name))
            else:
                default = token
        return cls(default=default, overrides=tuple(overrides))

    def describe(self) -> str:
        parts = ([] if self.default == "auto" else [self.default])
        parts += [f"{op}={name}" for op, name in self.overrides]
        return ",".join(parts) or "auto"


AUTO_POLICY = KernelPolicy()


class _PolicyStack(threading.local):
    def __init__(self):
        self.stack: list[KernelPolicy] = []


_POLICY = _PolicyStack()


def current_policy() -> KernelPolicy:
    """Active ambient policy: context manager > SPRING_KERNEL_IMPL > auto."""
    if _POLICY.stack:
        return _POLICY.stack[-1]
    env = os.environ.get(ENV_VAR)
    if env:
        return KernelPolicy.parse(env)
    return AUTO_POLICY


@contextlib.contextmanager
def kernel_policy(policy=None, /, default: Optional[str] = None, **per_op: str):
    """Scope an ambient kernel policy (tests, benchmarks, reports).

    ``kernel_policy("interpret")``, ``kernel_policy(default="ref")``,
    ``kernel_policy(ssd_scan="jnp")`` and ``kernel_policy(policy_obj)``
    all work; the previous policy is restored on exit.
    """
    if policy is None:
        policy = KernelPolicy(default=default or "auto",
                              overrides=tuple(sorted(per_op.items())))
    elif isinstance(policy, str):
        policy = KernelPolicy.parse(policy)
    elif not isinstance(policy, KernelPolicy):
        raise TypeError(f"expected KernelPolicy | str, got {type(policy)}")
    _POLICY.stack.append(policy)
    try:
        yield policy
    finally:
        _POLICY.stack.pop()


# ---------------------------------------------------------------------------
# Dispatch counters + instrumentation metrics.
#
# Both live in the process-wide telemetry MetricsRegistry
# (repro.telemetry.metrics.default_registry) rather than module-level
# dicts: one labeled home for every runtime measurement, with explicit
# snapshot()/reset() isolation (a conftest autouse fixture resets it per
# test, so counts no longer leak across tests and benchmarks sharing one
# process).  dispatch_counts()/reset_dispatch_counts() survive as the
# op-keyed views the dry-run and tests consume.
# ---------------------------------------------------------------------------

#: MetricsRegistry family names (the Prometheus-visible spellings).
DISPATCH_METRIC = "spring_kernel_dispatch_total"
KERNEL_METRIC_PREFIX = "spring_kernel_"


def _metrics_registry():
    from repro.telemetry.metrics import default_registry

    return default_registry()


def record_dispatch(op: str, name: str) -> None:
    """Count one dispatch of ``op`` to impl ``name``: :func:`resolve` does
    unless ``_count=False``; a lowering resolved ahead of its run counts
    where it runs."""
    _metrics_registry().inc(
        DISPATCH_METRIC, op=op, impl=name,
        help="kernel-registry resolutions per (op, impl)")


def dispatch_counts() -> dict[str, dict[str, int]]:
    """{op: {impl: resolutions}} since the last reset.

    Counts are *resolutions*: one per eager call, one per trace under jit
    (resolution is trace-time — the compiled program embeds the choice).
    """
    snap = _metrics_registry().snapshot().get(DISPATCH_METRIC)
    out: dict[str, dict[str, int]] = {}
    if snap is None:
        return out
    for cell in snap["cells"]:
        labels = cell["labels"]
        out.setdefault(labels["op"], {})[labels["impl"]] = int(cell["value"])
    return out


def reset_dispatch_counts() -> None:
    _metrics_registry().reset(DISPATCH_METRIC)


class _Metrics(threading.local):
    def __init__(self):
        self.rows: Optional[list] = None


_METRICS = _Metrics()


@contextlib.contextmanager
def record_kernel_metrics():
    """Collect per-op instrumentation rows from eager calls in the block.

    Ops contribute host-side scalars only when operands are concrete
    (mirrors ``memstash.instrument``): ``masked_matmul`` notes its
    tile-skip fraction, ``mask_pack`` its wire bytes.  Under jit tracing
    the hooks are no-ops, keeping compiled programs free of host syncs.
    """
    prev = _METRICS.rows
    _METRICS.rows = []
    try:
        yield _METRICS.rows
    finally:
        _METRICS.rows = prev


def metrics_recording() -> bool:
    return _METRICS.rows is not None


def metrics_active() -> bool:
    """Should eager hooks compute their host-side scalars?  True inside a
    ``record_kernel_metrics`` block *or* when a telemetry scope is active
    — the scalars cost a device read, so they stay gated either way."""
    if _METRICS.rows is not None:
        return True
    from repro import telemetry

    return telemetry.enabled()


def note_metric(op: str, **values: float) -> None:
    """Record one eager instrumentation row.

    Rows flow to the thread-local recorder (the ``record_kernel_metrics``
    API perfmodel consumes) and, always, into the telemetry
    MetricsRegistry as labeled histograms
    (``spring_kernel_<key>{op=...}``) so ``serve --json`` /
    ``benchmarks/run.py --json`` snapshots carry them.
    """
    if _METRICS.rows is not None:
        _METRICS.rows.append(dict(values, op=op))
    reg = _metrics_registry()
    for key, v in values.items():
        reg.observe(KERNEL_METRIC_PREFIX + key, float(v), op=op,
                    help=f"eager kernel instrumentation: {key} per op")


def metric_summary(rows: list) -> dict[str, dict[str, float]]:
    """Mean of each recorded metric key per op: {op: {key: mean}}."""
    acc: dict[str, dict[str, list]] = {}
    for row in rows:
        op = row["op"]
        for k, v in row.items():
            if k == "op":
                continue
            acc.setdefault(op, {}).setdefault(k, []).append(float(v))
    return {op: {k: sum(v) / len(v) for k, v in kv.items()}
            for op, kv in acc.items()}


# ---------------------------------------------------------------------------
# Resolution.
# ---------------------------------------------------------------------------


def _auto_pick(op: str, **caps: Any) -> KernelImpl:
    cands = [
        k for k in _IMPLS[op].values()
        if k.selectable and k.available() and k.supports_call(**caps)
    ]
    if not cands:
        raise ValueError(
            f"kernel op {op!r}: no available implementation on backend "
            f"{jax.default_backend()!r} for capabilities {caps}")
    return max(cands, key=lambda k: k.priority)


def resolve(op: str, impl: Optional[str] = None, *, _count: bool = True,
            **caps: Any) -> KernelImpl:
    """Resolve one op invocation to a registered implementation.

    ``impl``: explicit call-site choice (wins), ``None``/``"auto"`` to
    defer to the ambient policy.  Capability kwargs (e.g.
    ``return_state=True``) constrain auto-selection and validate explicit
    picks — a pinned impl that cannot serve the call raises a
    ``ValueError`` naming the impl and the ops that could.

    ``_count=False`` marks a *planning* resolution (config threading,
    resolution tables, a lowering resolved ahead of its run): it is
    excluded from ``dispatch_counts()`` so only the invocation counts.
    """
    ensure_registered()
    if op not in _OPS:
        raise KeyError(f"unknown kernel op {op!r}; registered: {sorted(_OPS)}")

    strict = True
    requested = impl if impl not in (None, "auto") else None
    if requested is None:
        pol = current_policy()
        over = dict(pol.overrides).get(op)
        if over is not None and over != "auto":
            requested = over
        elif pol.default != "auto":
            requested, strict = pol.default, False

    if requested is None:
        kimpl = _auto_pick(op, **caps)
    else:
        if requested not in IMPL_NAMES:
            raise ValueError(
                f"unknown kernel impl {requested!r} for op {op!r}; "
                f"choose from {('auto',) + IMPL_NAMES}")
        kimpl = _IMPLS[op].get(requested)
        if kimpl is None:
            if strict:
                raise ValueError(
                    f"kernel op {op!r} has no {requested!r} implementation; "
                    f"registered: {sorted(_IMPLS[op])}")
            kimpl = _auto_pick(op, **caps)  # soft global default
        elif not kimpl.available():
            raise ValueError(
                f"kernel op {op!r} impl {requested!r} is not available on "
                f"backend {jax.default_backend()!r}")
        elif not kimpl.supports_call(**caps):
            if strict:
                ok = sorted(n for n, k in _IMPLS[op].items()
                            if k.supports_call(**caps))
                raise ValueError(
                    f"kernel op {op!r}: impl {requested!r} does not support "
                    f"{caps}; supported by: {ok or 'none'}")
            kimpl = _auto_pick(op, **caps)
    if _count:
        record_dispatch(op, kimpl.name)
    return kimpl


def policy_scope(policy: Optional[KernelPolicy]):
    """Scope a config-threaded policy (SpringConfig.kernels): an ``auto``
    policy defers to the ambient one (context manager / env var); a
    concrete one is pushed, so its global default keeps soft-fallback
    semantics."""
    if policy is None or policy.is_auto:
        return contextlib.nullcontext()
    return kernel_policy(policy)


def resolve_with(policy: Optional[KernelPolicy], op: str, **caps: Any) -> KernelImpl:
    """Resolve ``op`` under a config-threaded policy (:func:`policy_scope`).

    This is a *planning* resolution (the chosen impl name is then passed
    to the public wrapper, which resolves again), so it does not count as
    a dispatch.
    """
    with policy_scope(policy):
        return resolve(op, _count=False, **caps)


def resolution_table(policy: Optional[KernelPolicy] = None,
                     **caps_by_op: dict) -> dict[str, str]:
    """{op: impl-or-error} the given (or ambient) policy resolves to now.

    Never raises: errors are reported inline as ``"error: ..."`` so the
    table can be embedded in dry-run / benchmark JSON unconditionally.
    An ``auto`` policy is not pushed (mirrors ``resolve_with``), so the
    table reflects the ambient env/context policy the calls actually saw.
    """
    ensure_registered()
    out = {}
    with policy_scope(policy):
        for op in sorted(_OPS):
            try:
                out[op] = resolve(op, _count=False, **caps_by_op.get(op, {})).name
            except (ValueError, KeyError) as e:
                out[op] = f"error: {e}"
    return out


def capability_table() -> dict[str, dict[str, dict]]:
    """Static view for docs/tests: {op: {impl: {available, selectable,
    priority, oracle}}} on the current backend."""
    ensure_registered()
    out: dict[str, dict[str, dict]] = {}
    for op in sorted(_OPS):
        out[op] = {
            name: {
                "available": bool(k.available()),
                "selectable": k.selectable,
                "priority": k.priority,
                "oracle": name == _OPS[op].oracle,
            }
            for name, k in sorted(_IMPLS[op].items())
        }
    return out


def compare_outputs(op: str, got: Any, want: Any,
                    case_compare: Optional[dict] = None) -> float:
    """Check ``got`` against the oracle output ``want`` under the op's
    registered comparison spec (or a per-case override), raising
    AssertionError on violation.  Returns the measured deviation (0.0 for
    exact specs) — the parity harness and the bench smoke sweep both use
    this, so the OpSpec.compare contract has exactly one interpreter.
    """
    import numpy as np

    spec = case_compare or op_spec(op).compare_spec()
    got_l = jax.tree_util.tree_leaves(got)
    want_l = jax.tree_util.tree_leaves(want)
    assert len(got_l) == len(want_l)
    worst = 0.0
    for g, w in zip(got_l, want_l):
        g = np.asarray(g, dtype=np.float64)
        w = np.asarray(w, dtype=np.float64)
        if spec["kind"] == "exact":
            assert (g == w).all(), f"{op}: impl must be bit-identical to oracle"
        elif spec["kind"] == "allclose":
            err = float(np.max(np.abs(g - w))) if g.size else 0.0
            assert err <= spec["atol"] + spec.get("rtol", 0.0) * float(np.max(np.abs(w))), \
                f"{op}: max err {err} > atol {spec['atol']}"
            worst = max(worst, err)
        elif spec["kind"] == "rel":
            denom = float(np.max(np.abs(w))) + 1e-12
            rel = float(np.max(np.abs(g - w))) / denom
            assert rel <= spec["tol"], f"{op}: rel err {rel} > {spec['tol']}"
            worst = max(worst, rel)
        else:
            raise ValueError(f"unknown compare kind {spec['kind']!r}")
    return worst


def parity_pairs() -> list[tuple[str, str]]:
    """Every (op, impl) pair the parity harness should cross-check against
    the op's oracle on the *current* backend."""
    ensure_registered()
    pairs = []
    for op in sorted(_OPS):
        oracle = _OPS[op].oracle
        for name, k in sorted(_IMPLS[op].items()):
            if name == oracle or not k.parity or not k.available():
                continue
            pairs.append((op, name))
    return pairs
