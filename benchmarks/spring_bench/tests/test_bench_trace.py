"""The reduction of a profiler trace to device ops, harness spans, idle
share, kernel time and the breakdown: on a small trace recorded on a TPU
v5e (a jitted step with the masked_matmul Pallas kernel, three times,
under harness spans), and on a hand-made trace laid out as the TPU
profiler lays it out."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import tracing

RECORDED = Path(__file__).resolve().parent / "small_tpu_trace.xplane.pb"


def test_recorded_tpu_trace():
    import jax

    tr = tracing.reduce_profile(jax.profiler.ProfileData.from_file(str(RECORDED)))
    assert [s.name for s in tr.spans] == ["bench.window"] + ["bench.dispatch", "bench.loss_read"] * 3
    mm = tr.matching(r"^_mm_kernel(\.|$)", module=r"^jit_step$")
    assert len(mm) == 3 and all(op.name == "_mm_kernel.1" for op in mm)
    assert tr.seconds(mm) == pytest.approx(7.114e-06)
    assert 0 < tr.busy_s() < tr.window_s and 0.99 < tr.idle_share() < 1
    assert tr.top_ops(1) == [["_mm_kernel.1", pytest.approx(7.114e-06)]]
    gaps = tr.idle_gaps(3)
    assert gaps[0][0] == "bench.loss_read" and gaps[0][1] == pytest.approx(0.169792165)


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


def op(name, start, dur):
    return ev(f"%{name} = f32[8,128]{{1,0}} op(f32[8,128]{{1,0}} %_mm_kernel.7)", start, dur,
              long_name="the op's HLO text")


def profile():
    ops = [
        ev("%while.3 = (s32[]) while(...)", 100, 300),  # control flow spans its body
        op("fusion.1", 100, 50),
        op("_mm_kernel.7", 140, 60),
        op("_mm_kernel.8", 300, 100),
        op("fusion.2", 700, 100),
        op("fusion.3", 950, 100),  # ends past the window
    ]
    modules = [ev("jit_step(123)", 100, 300), ev("jit_other(456)", 690, 400)]
    host = [ev("bench.window", 100, 900), ev("bench.data", 200, 100),
            ev("bench.loss_read", 400, 300), ev("unrelated", 0, 5)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=modules),
                                        NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
    ])


def test_ops_and_spans_are_kept():
    tr = tracing.reduce_profile(profile())
    assert len(tr.ops) == 6 and {o.module for o in tr.ops} == {"jit_step", "jit_other"}
    assert {o.name for o in tr.ops} >= {"fusion.1", "_mm_kernel.7", "while.3"}
    assert [s.name for s in tr.spans] == ["bench.window", "bench.data", "bench.loss_read"]
    assert tr.window == (100.0, 1000.0)
    assert tr.window_s == pytest.approx(900e-9)


def test_idle_share_is_one_minus_the_union_over_the_window():
    tr = tracing.reduce_profile(profile())
    # busy: [100, 200) overlapping ops merged, [300, 400), [700, 800), [950, 1000) clipped
    assert tr.busy_s() == pytest.approx((100 + 100 + 100 + 50) * 1e-9)
    assert tr.idle_share() == pytest.approx(1 - 350 / 900)


def test_kernel_time_by_name_and_module():
    tr = tracing.reduce_profile(profile())
    # by instruction name, not by an operand named in another op's text
    mm = tr.matching(r"^_mm_kernel(\.|$)")
    assert len(mm) == 2 and tr.seconds(mm) == pytest.approx(160e-9)
    assert tr.matching(r"^fusion", module=r"jit_other") == [o for o in tr.ops if o.name in ("fusion.2", "fusion.3")]
    assert len(tr.matching(r"^fusion", module=r"jit_step")) == 1


def test_breakdown_top_ops_and_idle_gaps():
    tr = tracing.reduce_profile(profile())
    top = tr.top_ops(2)
    assert [name for name, _ in top] == ["_mm_kernel.8", "fusion.2"]  # not while.3
    gaps = tr.idle_gaps(10)
    # gaps inside the window: [200,300) under bench.data, [400,700) under
    # bench.loss_read, [800,950) under no harness span
    assert gaps[0] == ["bench.loss_read", pytest.approx(300e-9)]
    assert sorted(g[0] for g in gaps) == ["bench.data", "bench.loss_read", "no harness span"]


def test_merge_is_a_union():
    assert tracing.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
