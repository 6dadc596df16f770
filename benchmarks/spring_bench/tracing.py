"""A JAX profiler trace of the measured window, reduced to device ops and
harness spans on one clock.

The harness wraps its calls in ``jax.profiler.TraceAnnotation("bench.*")``
so that its spans land in the profiler's own trace, on the clock of the
device ops.  The reduction keeps, per chip, the ops of the ``XLA Ops``
line with their module and the text of their stats (the HLO op name and
the framework op path that a kernel's or a jitted function's name shows
up in), and the ``bench.*`` spans of the host.  The metric readers under
``metrics/`` read only this.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Optional

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
CONTAINER = re.compile(r"(while|conditional|call)(\.|$)")


@dataclasses.dataclass(frozen=True)
class Op:
    device: str
    name: str
    start_ns: float
    dur_ns: float
    module: str
    text: str  # the op's event name and every string stat (its HLO text)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


def merge(intervals) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


@dataclasses.dataclass
class Trace:
    ops: list
    spans: list
    window: tuple  # (start_ns, end_ns) of the measured window

    @property
    def devices(self) -> list:
        return sorted({op.device for op in self.ops})

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, device: str) -> list:
        lo, hi = self.window
        return merge((max(op.start_ns, lo), min(op.end_ns, hi))
                     for op in self.ops
                     if op.device == device and op.end_ns > lo and op.start_ns < hi
                     and not CONTAINER.match(op.name))

    def busy_s(self) -> float:
        """Seconds in which an op ran on the device inside the window,
        averaged over the chips that ran any; control flow, whose events
        span the ops of their bodies, counts through those ops."""
        devs = self.devices
        if not devs:
            return 0.0
        total = sum(e - s for d in devs for s, e in self._busy(d))
        return total / len(devs) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def matching(self, pattern: str, module: Optional[str] = None) -> list:
        """Ops whose instruction name matches ``pattern`` (a regular
        expression), optionally only inside modules matching ``module``."""
        rx = re.compile(pattern)
        mx = re.compile(module) if module else None
        return [op for op in self.ops if rx.search(op.name)
                and (mx is None or mx.search(op.module))]

    def seconds(self, ops) -> float:
        return sum(op.dur_ns for op in ops) / 1e9

    def top_ops(self, n: int = 10) -> list:
        """The ops that took most device time, summed by instruction name
        over the chips, as [name, seconds].  Control flow (``while``,
        ``conditional``, ``call``), whose events span the ops of their
        bodies, is left out."""
        by = {}
        for op in self.ops:
            if CONTAINER.match(op.name):
                continue
            by[op.name] = by.get(op.name, 0.0) + op.dur_ns
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def span_at(self, t_ns: float) -> str:
        """The innermost harness span open at ``t_ns``."""
        best = None
        for sp in self.spans:
            if sp.name != WINDOW_SPAN and sp.start_ns <= t_ns <= sp.end_ns:
                if best is None or sp.end_ns - sp.start_ns < best.end_ns - best.start_ns:
                    best = sp
        return best.name if best is not None else "no harness span"

    def idle_gaps(self, n: int = 10) -> list:
        """The longest stretches inside the window in which the first chip
        ran no op, each as [what the host was doing, seconds]."""
        devs = self.devices
        if not devs:
            return []
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self._busy(devs[0]):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.span_at((s + e) / 2), (e - s) / 1e9] for s, e in gaps[:n]]


def _assign_modules(ops: list, modules: list) -> None:
    """Name each op's module (``jit_<function>``) by the ``XLA Modules``
    event that contains its start."""
    ops.sort(key=lambda op: op.start_ns)
    i = 0
    for op in ops:
        while i < len(modules) and modules[i][1] < op.start_ns:
            i += 1
        if not op.module and i < len(modules) and modules[i][0] <= op.start_ns:
            object.__setattr__(op, "module", modules[i][2])


def short_name(name: str) -> str:
    """An op's HLO instruction name: the TPU profiler names an op event by
    its whole HLO text (``%_mm_kernel.92 = f32[...] custom-call(...)``);
    a Pallas kernel's instruction is named after its kernel function."""
    return name.split(" = ", 1)[0].lstrip("%")


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        out[k] = v
    return out


def reduce_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace`."""
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                              ev.name.split("(", 1)[0])
                             for ev in lines.get(MODULES_LINE, []))
            for ev in lines.get(OPS_LINE, []):
                st = _stats(ev)
                text = " ".join([ev.name] + [v for v in st.values() if isinstance(v, str)])
                ops.append(Op(device=plane.name, name=short_name(ev.name),
                              start_ns=float(ev.start_ns), dur_ns=float(ev.duration_ns),
                              module=str(st.get("hlo_module", "")), text=text))
            _assign_modules([op for op in ops if op.device == plane.name], modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, float(ev.start_ns),
                                          float(ev.start_ns + ev.duration_ns)))
    windows = [sp for sp in spans if sp.name == WINDOW_SPAN]
    if windows:
        window = (windows[0].start_ns, windows[0].end_ns)
    elif ops:
        window = (min(o.start_ns for o in ops), max(o.end_ns for o in ops))
    else:
        window = (0.0, 1.0)
    return Trace(ops=ops, spans=spans, window=window)


class Profiled:
    """``with Profiled() as p: ...`` traces the block with the JAX
    profiler into a temporary directory (under ``TMPDIR``), reduces it,
    and deletes the files; the reduction is ``p.trace`` afterwards."""

    def __init__(self):
        self.trace: Optional[Trace] = None
        self._dir = ""

    def __enter__(self):
        import jax

        self._dir = tempfile.mkdtemp(prefix="spring_bench_trace_")
        jax.profiler.start_trace(self._dir)
        return self

    def __exit__(self, *exc):
        import jax

        try:
            jax.profiler.stop_trace()
            if exc[0] is None:
                files = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                                  recursive=True)
                if not files:
                    raise RuntimeError("the profiler wrote no trace")
                self.trace = reduce_profile(jax.profiler.ProfileData.from_file(files[0]))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return None
