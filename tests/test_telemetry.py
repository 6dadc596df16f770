"""spring-trace seals (ISSUE 6).

Four contracts:

  1. the quantile sketch is mergeable (associative/commutative), exact
     under small n, and rank-accurate within its alpha bound past the
     exact phase — hypothesis properties;
  2. the MetricsRegistry snapshot/reset/restore API isolates global
     counter state (and the kernel dispatch counters ride on it);
  3. exported traces satisfy the Chrome trace-event schema and carry the
     tick/step span taxonomy;
  4. the parity seal: train losses and serve tokens are bit-identical
     with telemetry on vs off (enabling measurement must never change
     what is computed), and engine results carry latency attribution.
"""

import dataclasses
import json
import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.telemetry import (
    MetricsRegistry,
    QuantileSketch,
    SpanTracer,
    TelemetryConfig,
    validate_chrome_trace,
)
from repro.telemetry.metrics import prometheus_from_snapshot, render_snapshot_table

pytestmark = pytest.mark.telemetry

# -- 1. quantile sketch properties -------------------------------------------

finite = st.floats(min_value=-1e9, max_value=1e9,
                   allow_nan=False, allow_infinity=False)


def _sk(values, alpha=0.01, max_exact=128):
    return QuantileSketch(alpha=alpha, max_exact=max_exact).update(values)


@given(st.lists(finite, max_size=60), st.lists(finite, max_size=60),
       st.lists(finite, max_size=60))
def test_sketch_merge_associative(a, b, c):
    """(a + b) + c == a + (b + c), state-for-state (canonical equality),
    and both orders agree with direct single-sketch ingestion."""
    sa, sb, sc = _sk(a), _sk(b), _sk(c)
    left = sa.merge(sb).merge(sc)
    right = sa.merge(sb.merge(sc))
    assert left == right
    assert left == _sk(a).merge(_sk(b).merge(_sk(c)))
    assert left.count == len(a) + len(b) + len(c)


@given(st.lists(finite, max_size=60), st.lists(finite, max_size=60))
def test_sketch_merge_commutative(a, b):
    assert _sk(a).merge(_sk(b)) == _sk(b).merge(_sk(a))


@given(st.lists(finite, min_size=1, max_size=128),
       st.floats(min_value=0.0, max_value=1.0))
def test_sketch_exact_under_small_n(values, q):
    """At or under max_exact samples every quantile is the exact
    nearest-rank order statistic — no approximation in tests/smokes."""
    sk = _sk(values)
    assert sk.is_exact
    rank = max(1, math.ceil(q * len(values)))
    assert sk.quantile(q) == sorted(values)[rank - 1]


@given(st.lists(st.floats(min_value=1e-3, max_value=1e6,
                          allow_nan=False, allow_infinity=False),
                min_size=129, max_size=400),
       st.floats(min_value=0.0, max_value=1.0))
@settings(max_examples=25)
def test_sketch_relative_error_bound(values, q):
    """Past the exact phase, the estimate at any quantile is within
    alpha relative error of the true nearest-rank order statistic
    (positive-value streams: the DDSketch guarantee)."""
    alpha = 0.01
    sk = _sk(values, alpha=alpha)
    assert not sk.is_exact
    rank = max(1, math.ceil(q * len(values)))
    true = sorted(values)[rank - 1]
    got = sk.quantile(q)
    assert abs(got - true) <= alpha * true + 1e-12


def test_sketch_latency_scale_past_exact_phase():
    """Deterministic regression: sub-1.0 samples (the latency-in-seconds
    regime the serving engine actually feeds the sketch) past max_exact
    must honour the alpha relative-error bound.  A sign-mirrored bucket
    index space collides here — positive values < 1.0 have *negative*
    magnitude indices — collapsing every percentile to min."""
    alpha = 0.01
    n = 1000
    values = [0.001 + 0.499 * k / (n - 1) for k in range(n)]  # all in (0, 1)
    sk = _sk(values, alpha=alpha)
    assert not sk.is_exact
    for q in (0.5, 0.95, 0.99):
        rank = max(1, math.ceil(q * n))
        true = sorted(values)[rank - 1]
        got = sk.quantile(q)
        assert abs(got - true) <= alpha * true, (q, got, true)
    # mixed signs with sub-1.0 magnitudes must order correctly too
    mixed = [(-1) ** k * (0.01 + 0.9 * k / 399) for k in range(400)]
    sk2 = _sk(mixed)
    assert not sk2.is_exact
    assert sk2.quantile(0.0) == sk2.min < 0 < sk2.max == sk2.quantile(1.0)
    true_med = sorted(mixed)[math.ceil(0.5 * len(mixed)) - 1]
    got_med = sk2.quantile(0.5)
    assert abs(got_med - true_med) <= 0.01 * abs(true_med)


@given(st.lists(finite, max_size=200))
def test_sketch_serialization_roundtrip(values):
    sk = _sk(values)
    back = QuantileSketch.from_dict(json.loads(json.dumps(sk.to_dict())))
    assert back == sk
    assert back.count == sk.count and back.sum == sk.sum


def test_sketch_rejects_nan_and_bad_params():
    with pytest.raises(ValueError):
        QuantileSketch().add(float("nan"))
    with pytest.raises(ValueError):
        QuantileSketch(alpha=0.0)
    with pytest.raises(ValueError):
        _sk([1.0]).merge(_sk([2.0], alpha=0.5))


def test_sketch_extrema_and_empty():
    sk = QuantileSketch()
    assert sk.quantile(0.5) == 0.0 and sk.mean == 0.0
    sk.update([5.0, -3.0, 0.0] + [1.0] * 200)  # force bucketed phase
    assert not sk.is_exact
    assert sk.min == -3.0 and sk.max == 5.0
    assert sk.quantile(0.0) >= sk.min and sk.quantile(1.0) <= sk.max


# -- 2. metrics registry ------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.inc("c_total", op="matmul")
    reg.inc("c_total", 2.0, op="matmul")
    reg.set("g", 0.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.observe("h", v)
    assert reg.get("c_total", op="matmul") == 3.0
    assert reg.get("g") == 0.5
    assert reg.get("h").count == 4
    snap = reg.snapshot()
    assert snap["c_total"]["kind"] == "counter"
    hcell = snap["h"]["cells"][0]
    assert hcell["count"] == 4 and hcell["p50"] == 2.0
    with pytest.raises(ValueError):
        reg.inc("c_total", -1.0, op="matmul")
    with pytest.raises(ValueError):
        reg.set("c_total", 1.0)  # kind clash


def test_registry_snapshot_reset_restore_isolation():
    reg = MetricsRegistry()
    reg.inc("a_total", 5.0)
    saved = reg.snapshot()
    reg.inc("a_total", 7.0)
    reg.set("b", 1.0)
    reg.reset()
    assert reg.names() == []
    reg.restore(saved)
    assert reg.get("a_total") == 5.0
    assert reg.snapshot() == saved
    reg.reset("a_total")
    assert reg.get("a_total") is None


def test_registry_snapshot_is_json_and_prom_renderable():
    reg = MetricsRegistry()
    reg.inc("spring_kernel_dispatch_total", op="masked_matmul", impl="ref")
    reg.observe("lat_s", 0.25, op="decode")
    snap = json.loads(json.dumps(reg.snapshot()))
    prom = prometheus_from_snapshot(snap)
    assert "# TYPE spring_kernel_dispatch_total counter" in prom
    assert '# TYPE lat_s summary' in prom
    assert 'lat_s{op="decode",quantile="0.5"} 0.25' in prom
    assert "lat_s_count" in prom and "lat_s_sum" in prom
    table = render_snapshot_table(snap)
    assert "spring_kernel_dispatch_total" in table and "p50" in table


def test_dispatch_counters_ride_on_default_registry():
    """The kernel registry's dispatch counters are MetricsRegistry cells
    now; the legacy dispatch_counts()/reset API reads/clears the same
    state, and the conftest fixture isolates it per test."""
    import jax.numpy as jnp

    from repro.kernels import registry
    from repro.kernels.masked_matmul.ops import masked_matmul
    from repro.telemetry import default_registry

    registry.reset_dispatch_counts()
    assert registry.dispatch_counts() == {}
    a = jnp.ones((8, 8)) * jnp.asarray(
        np.random.default_rng(0).random((8, 8)) > 0.5, jnp.float32)
    masked_matmul(a, jnp.ones((8, 8)))
    counts = registry.dispatch_counts()
    assert sum(counts.get("masked_matmul", {}).values()) >= 1
    cell = default_registry().get(
        registry.DISPATCH_METRIC, op="masked_matmul",
        impl=next(iter(counts["masked_matmul"])))
    assert cell is not None and cell >= 1
    registry.reset_dispatch_counts()
    assert registry.dispatch_counts() == {}


# -- 3. span tracer + trace schema -------------------------------------------


def test_tracer_records_and_exports_valid_trace(tmp_path):
    tr = SpanTracer()
    with tr.span("serve.tick", tick=0):
        with tr.span("serve.tick.decode", active=2):
            pass
    path = tr.write(str(tmp_path / "t.json"), extra_metadata={"run": "test"})
    events = validate_chrome_trace(open(path).read())
    names = [e["name"] for e in events]
    assert set(names) == {"serve.tick", "serve.tick.decode"}
    complete = [e for e in events if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in complete)
    # child closed before parent: appears first, nested inside in time
    decode = next(e for e in complete if e["name"] == "serve.tick.decode")
    tick = next(e for e in complete if e["name"] == "serve.tick")
    assert tick["ts"] <= decode["ts"]
    assert decode["ts"] + decode["dur"] <= tick["ts"] + tick["dur"] + 1e-6


def test_tracer_sampling_is_deterministic_and_tree_scoped():
    tr = SpanTracer(sample_rate=0.5)
    for i in range(10):
        with tr.span("root", i=i):
            with tr.span("child"):
                pass
    events = tr.events()
    roots = [e for e in events if e["name"] == "root"]
    children = [e for e in events if e["name"] == "child"]
    # accumulator: exactly ceil(10 * 0.5) roots, each with its child
    assert len(roots) == 5 and len(children) == 5
    tr2 = SpanTracer(sample_rate=0.5)
    for i in range(10):
        with tr2.span("root", i=i):
            pass
    assert [e["args"]["i"] for e in tr2.events()
            ] == [e["args"]["i"] for e in roots]
    with pytest.raises(ValueError):
        SpanTracer(sample_rate=0.0)


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"no": "traceEvents"})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "Q"}]})
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0,
                              "dur": -1.0, "pid": 1, "tid": 1}]})


def test_ambient_scope_activates_and_restores():
    from repro import telemetry

    assert telemetry.tracer() is None
    with telemetry.span("noop"):  # disabled path: shared null span
        pass
    assert telemetry.span("a") is telemetry.span("b")
    with telemetry.scope(TelemetryConfig(enabled=True)) as tr:
        assert telemetry.enabled() and telemetry.tracer() is tr
        with telemetry.span("serve.tick"):
            pass
        assert len(tr) == 1
    assert telemetry.tracer() is None
    with telemetry.scope(None) as tr:
        assert tr is None and not telemetry.enabled()


# -- 4. session parity seal + latency attribution -----------------------------


def _serve_specs(tmp_path):
    from repro.api.sessions import serve_spec
    from repro.api.spec import TelemetrySection

    spec = serve_spec("llama3.2-1b", batch=2, prompt_len=8, gen=4,
                      slots=2, queue=3, mode="quant_sparse")
    spec_on = dataclasses.replace(spec, telemetry=TelemetrySection(
        enabled=True, trace_path=str(tmp_path / "serve_trace.json")))
    return spec, spec_on


@pytest.mark.slow
def test_serve_parity_and_attribution_with_telemetry(tmp_path):
    """The acceptance seal: telemetry on vs off is bit-identical on
    generated tokens; the on-run emits a valid trace with tick-phase
    spans and per-request TTFT/queue/tick attribution."""
    from repro.api.sessions import session_for

    spec, spec_on = _serve_specs(tmp_path)
    out_off = session_for(spec).run()
    out_on = session_for(spec_on).run()
    assert np.array_equal(np.asarray(out_off["generated"]),
                          np.asarray(out_on["generated"]))
    assert "telemetry" not in out_off

    events = validate_chrome_trace(
        open(tmp_path / "serve_trace.json").read())
    names = {e["name"] for e in events}
    assert {"serve.tick", "serve.tick.schedule", "serve.tick.prefill",
            "serve.tick.install", "serve.tick.decode", "serve.tick.sample",
            "serve.tick.repack"} <= names

    for out in (out_off, out_on):  # attribution is always-on engine state
        la = out["latency"]
        for k in ("queue_s", "ttft_s", "token_s"):
            assert set(la[k]) == {"p50", "p95", "p99"}
        assert 0.0 < la["tick_utilization"] <= 1.0
        for r in out["per_request"]:
            assert r["enqueue_tick"] >= 0
            assert r["first_token_tick"] >= r["enqueue_tick"]
            assert r["finish_tick"] >= r["first_token_tick"]
            assert r["decode_ticks"] == r["n_tokens"]
            assert r["ttft_s"] >= r["queue_s"] >= 0.0

    tel = out_on["telemetry"]
    assert tel["spans"] == len(events)
    snap = tel["metrics"]
    assert "spring_serve_tick_utilization" in snap
    assert "spring_kernel_dispatch_total" in snap
    json.dumps(tel)  # must be artifact-safe


@pytest.mark.slow
def test_train_parity_with_telemetry(tmp_path):
    """Train losses bit-identical on vs off; the trace carries the step
    phase taxonomy, and the jitted step names the memstash pack/unpack
    work with named scopes."""
    import jax
    import jax.numpy as jnp

    from repro.api.sessions import session_for, train_spec
    from repro.api.spec import TelemetrySection
    from repro.runtime.train import init_train_state, make_train_step

    spec = train_spec(steps=2, batch=2, seq=16, stash="stash")
    session = session_for(spec)
    out_off = session.run()
    trace = tmp_path / "train_trace.json"
    spec_on = dataclasses.replace(spec, telemetry=TelemetrySection(
        enabled=True, trace_path=str(trace)))
    out_on = session_for(spec_on).run()
    assert out_off["losses"] == out_on["losses"]
    names = {e["name"] for e in validate_chrome_trace(trace.read_text())}
    assert {"train.step", "train.step.data", "train.step.device",
            "train.step.host"} <= names
    r = session.resolved
    state = init_train_state(jax.random.PRNGKey(0), r.view, r.step, reduced=True)
    hlo = jax.jit(make_train_step(r.view, r.step)).lower(
        state, {"tokens": jnp.zeros((2, 16), jnp.int32)}).as_text(debug_info=True)
    assert "memstash_pack" in hlo and "memstash_unpack" in hlo


def test_spans_land_in_the_profiler_trace(tmp_path):
    """With a telemetry scope active, a span is also a JAX profiler
    annotation: it shows up in the profiler's own trace by name."""
    import glob

    import jax

    from repro import telemetry

    jax.profiler.start_trace(str(tmp_path))
    with telemetry.scope(telemetry.TelemetryConfig(enabled=True)):
        with telemetry.span("train.step.device"):
            jax.block_until_ready(jax.numpy.ones(4) * 2)
    with telemetry.span("outside.any.scope"):
        pass
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = {ev.name for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "train.step.device" in names and "outside.any.scope" not in names


def test_telemetry_spec_section_roundtrip():
    from repro.api.spec import RunSpec, SpecError, build_spec

    spec = build_spec("serve", sets=["telemetry.enabled=true",
                                    "telemetry.sample_rate=0.25"])
    assert spec.telemetry.enabled and spec.telemetry.sample_rate == 0.25
    assert spec.provenance["telemetry.enabled"].startswith("set:")
    back = RunSpec.from_dict(spec.to_dict())
    assert back.telemetry == spec.telemetry
    with pytest.raises(SpecError):
        build_spec("serve", sets=["telemetry.sample_rate=0"]).validate()


def test_report_cli_renders_artifact(tmp_path, capsys):
    from repro.telemetry import report

    reg = MetricsRegistry()
    reg.inc("spring_serve_tokens_total", 12.0)
    artifact = {
        "telemetry": {"metrics": reg.snapshot()},
        "per_request": [{"rid": 0, "queue_s": 0.01, "ttft_s": 0.02,
                         "latency_s": 0.05, "n_tokens": 4,
                         "enqueue_tick": 0, "first_token_tick": 1,
                         "finish_tick": 4}],
    }
    path = tmp_path / "run.json"
    path.write_text(json.dumps(artifact))
    report.main([str(path)])
    text = capsys.readouterr().out
    assert "spring_serve_tokens_total" in text
    assert "0->1->4" in text
    report.main([str(path), "--prom"])
    assert "# TYPE spring_serve_tokens_total counter" in capsys.readouterr().out
    tr = SpanTracer()
    with tr.span("serve.tick"):
        pass
    tpath = tr.write(str(tmp_path / "trace.json"))
    report.main(["--validate-trace", tpath])
    assert "1 events OK" in capsys.readouterr().out
    with pytest.raises(SystemExit):
        report.extract_snapshot({"something": "else"})
