"""The program's phase scopes and tile counter as the benchmark reads
them: the map from a compiled module's instructions to the innermost
scope, and each reader on a synthetic run, with and without what it
reads."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

import harness
import step_probe
import tracing

SCOPE_READERS = {
    "optimizer_ms_per_step.train": "spring_optimizer",
    "quantize_ms_per_step.train": "spring_quantize",
    "mm_prep_ms_per_step.train": "spring_mm_prep",
    "ssd_scan_vjp_ms_per_step.train": "spring_ssd_scan_vjp",
}
TILE_READER = "masked_matmul_tile_skip.train"

HLO = """HloModule jit_plain_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %round.1 = f32[4]{0} round-nearest-even(f32[4]{0} %param_0), metadata={op_name="jit(plain_step)/spring_optimizer/spring_quantize/round" source_file="x.py" source_line=3}
}

ENTRY %main.9 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.2 = f32[4]{0} fusion(f32[4]{0} %x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(plain_step)/transpose(jvp(spring_optimizer))/spring_quantize_like/mul"}
  %_mm_kernel.7 = f32[4]{0} custom-call(f32[4]{0} %fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(plain_step)/jit(_mm_kernel)/pallas_call"}
  %copy.3 = f32[4]{0} copy(f32[4]{0} %_mm_kernel.7)
  ROOT %while.4 = f32[4]{0} while(f32[4]{0} %copy.3), condition=%c, body=%b, metadata={op_name="jit(plain_step)/while \\"q\\" spring_mm_prep"}
}
"""


def test_instruction_scopes_take_the_innermost_scope():
    scopes = step_probe.instruction_scopes(HLO)
    assert step_probe.module_name(HLO) == "jit_plain_step"
    assert scopes == {
        "param_0": None,
        "round.1": "spring_quantize",       # inside spring_optimizer
        "x.1": None,
        "fusion.2": "spring_optimizer",     # wrapped by transpose(jvp(...))
        "_mm_kernel.7": None,
        "copy.3": None,                     # no metadata
        "while.4": "spring_mm_prep",        # escaped quotes in op_name
    }


def test_instruction_scopes_of_a_compiled_module():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("spring_optimizer"):
            y = jnp.exp(x)
            with jax.named_scope("spring_quantize"):
                y = jnp.round(y)
        return jnp.sin(y)

    text = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
    scopes = step_probe.instruction_scopes(text)
    kind = {name.split(".")[0]: s for name, s in scopes.items()}
    assert step_probe.module_name(text) == "jit_f"
    assert kind["exp"] == "spring_optimizer"
    assert kind["round"] == "spring_quantize"
    assert kind["sin"] is None


def _op(name, dur_ns, module="jit_plain_step"):
    return tracing.Op(device="/device:TPU:0", name=name, start_ns=0.0,
                      dur_ns=float(dur_ns), module=module, text=name)


def _run(report, trace=True):
    """A run of two steps on one chip whose step report is ``report``."""
    ops = [_op("fusion.2", 3e6), _op("fusion.5", 1e6), _op("_mm_kernel.7", 8e6),
           _op("while.4", 50e6), _op("fusion.9", 7e6, module="jit_other")]
    tr = tracing.Trace(ops=ops, spans=[], window=(0.0, 1e9)) if trace else None
    return NS(cell=None, trace=tr, counters={"steps": 2}, chips=1, step_report=report)


def _reader(name):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py")


REPORT = {"module": "jit_plain_step",
          "scopes": {"fusion.2": "spring_optimizer", "fusion.5": "spring_quantize",
                     "_mm_kernel.7": None, "while.4": "spring_optimizer",
                     "x.1": "spring_mm_prep", "y.1": "spring_ssd_scan_vjp"},
          "mm_tiles": [30.0, 40.0, 10.0, 40.0, 40.0, 40.0]}


@pytest.mark.parametrize("name,want", [
    ("optimizer_ms_per_step.train", 1.5),   # fusion.2 over 2 steps; the while spans its body
    ("quantize_ms_per_step.train", 0.5),
    ("mm_prep_ms_per_step.train", 0.0),     # named by the program, no op in the trace
    ("ssd_scan_vjp_ms_per_step.train", 0.0),
    (TILE_READER, 100.0 * (1 - 80 / 120)),
])
def test_readers_on_a_synthetic_run(name, want):
    assert _reader(name).read(_run(REPORT)) == pytest.approx(want)


@pytest.mark.parametrize("name", list(SCOPE_READERS) + [TILE_READER])
def test_readers_read_nothing_without_trace_or_map(name):
    """None without a step report, and for the scope readers without a
    trace or where the program names no instruction with their scope (as
    a program without the scopes, or the counter, reads)."""
    reader = _reader(name)
    assert reader.read(_run(None)) is None
    bare = {"module": "jit_plain_step", "scopes": {"fusion.2": None}, "mm_tiles": None}
    assert reader.read(_run(bare)) is None
    if name in SCOPE_READERS:
        assert reader.read(_run(REPORT, trace=False)) is None


def test_scope_seconds_splits_the_step_module():
    run = _run(dict(REPORT, scopes={"fusion.2": None, "fusion.5": "spring_quantize",
                                    "_mm_kernel.7": None}))
    got = step_probe.scope_seconds(run.trace, run.step_report)
    assert got == {"spring_quantize": 1e-3, "spring_mm_prep": 0.0, "spring_ssd_scan_vjp": 0.0,
                   "spring_optimizer": 0.0, "_mm_kernel": 8e-3, "_ssd_kernel": 0.0,
                   "unscoped": 3e-3, "unmapped": 0.0}
