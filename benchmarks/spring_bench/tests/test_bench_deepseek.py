"""DeepSeek-V2-Lite's cell as the benchmark reads it: the work counts by
hand, the cut's parameter count, the layer scopes and the MoE row counter
(``layer_probe.py`` and its three readers) on synthetic runs, and a whole
run and the controls of a throwaway tiny DeepSeek cell on the CPU."""

from __future__ import annotations

import dataclasses
import json
import shutil
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import controls
import harness
import layer_probe
import run_cell
import tracing

BENCH = harness.BENCH_DIR
CONFIG = harness.load_json(BENCH / "configs" / "deepseek-v2-lite.json")
WORK = harness.load_module(BENCH / "work" / "deepseek-v2-lite.py")
REF = harness.load_module(BENCH / "reference" / "deepseek-v2-lite.py")
CELL = "deepseek-v2-lite.train.qs.4k"


def test_model_flops_by_hand():
    """Per token at the cut (seq 4096): 2 x the active weights of every
    product, plus the causal scores.  Active weights: per layer MLA
    2048*3072 + 2048*512 + 2048*64 + 2*512*2048 + 2048*2048 = 13,762,560;
    layer 0's MLP 3*2048*10944; per MoE layer the router 2048*64, the
    shared experts 3*2048*2816 and 6*8/64 of an expert 3*2048*1408; the
    head 2048*12800.  Scores: 16 heads x (192 + 128) x 4097/2 per layer."""
    active = 6 * 13_762_560 + 3 * 2048 * 10944 \
        + 5 * (2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408) + 2048 * 12800
    scores = 6 * 2 * 16 * 320 * 4097 / 2
    assert WORK.forward_flops_per_token(CONFIG, 4096) == pytest.approx(2 * active + scores)
    assert WORK.train_flops_per_token(CONFIG, 4096) == pytest.approx(2_151_376_896)


def test_matmuls_are_every_kernel_product():
    calls = WORK.matmuls(CONFIG, 1, 4096)
    # 6 layers x 4 MLA projections, layer 0's 3 MLP products, and per MoE
    # layer 3 shared-expert products and 3 for each of the 8 held experts
    assert len(calls) == 6 * 4 + 3 + 5 * (3 + 3 * 8)
    assert calls[:7] == [(4096, 2048, 3072), (4096, 2048, 512), (4096, 2048, 64),
                         (4096, 2048, 2048), (4096, 2048, 10944), (4096, 2048, 10944),
                         (4096, 10944, 2048)]
    assert calls.count((4096, 2048, 1408)) == 5 * 8 * 2
    assert calls.count((4096, 1408, 2048)) == 5 * 8


def test_the_cut_holds_about_635m_parameters():
    """81.0M in layer 0, 100.4M in each MoE layer, 52.4M in the embedding
    and the head."""
    import jax

    shapes = jax.eval_shape(lambda: REF.init_params(CONFIG, jax.random.PRNGKey(0)))
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))  # noqa: E731
    assert count(shapes) == 635_466_752
    assert count(shapes["prefix_0"]) / 1e6 == pytest.approx(81.0, abs=0.05)
    assert count(shapes["unit_0"]) / 5e6 == pytest.approx(100.4, abs=0.05)
    assert (count(shapes["embed"]) + count(shapes["lm_head"])) / 1e6 == pytest.approx(52.4, abs=0.05)


def test_the_configuration_file_states_the_programs_widths():
    """``program_config`` applies the cut to the registry's published
    config and finds every stated width; a program without an expert
    share fails at once."""
    from repro.configs import ARCHS

    cell = harness.load_cell(CELL)
    cfg = harness.program_config(cell, ARCHS["deepseek-v2-lite-16b"].config)
    assert (cfg.n_layers, cfg.experts_held, cfg.vocab, cfg.moe.n_experts) == (6, 8, 12800, 64)
    assert set(CONFIG["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}

    @dataclasses.dataclass(frozen=True)
    class Parent:  # a program whose config has no experts_held
        n_layers: int = 27
        n_units: int = 26
        vocab: int = 102400

    with pytest.raises(TypeError):
        harness.program_config(cell, Parent())


HLO = """HloModule jit_plain_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

ENTRY %main.9 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %x.1), kind=kLoop, calls=%c, metadata={op_name="jit(plain_step)/spring_moe_dispatch/jit(_take)/select_n"}
  %fusion.2 = f32[4]{0} fusion(f32[4]{0} %fusion.1), kind=kLoop, calls=%c, metadata={op_name="jit(plain_step)/transpose(jvp(spring_moe_combine))/mul"}
  %fusion.3 = f32[4]{0} fusion(f32[4]{0} %fusion.2), kind=kLoop, calls=%c, metadata={op_name="jit(plain_step)/spring_mla_attention/spring_quantize/round"}
  %fusion.4 = f32[4]{0} fusion(f32[4]{0} %fusion.3), kind=kLoop, calls=%c, metadata={op_name="jit(plain_step)/spring_mla_attention/dot_general"}
  ROOT %_mm_kernel.5 = f32[4]{0} custom-call(f32[4]{0} %fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(plain_step)/jit(_mm_kernel)/pallas_call"}
}
"""


def test_instruction_scopes_know_the_layers():
    assert layer_probe.instruction_scopes(HLO) == {
        "x.1": None, "fusion.1": "spring_moe_dispatch", "fusion.2": "spring_moe_combine",
        "fusion.3": "spring_quantize",  # innermost, inside the attention
        "fusion.4": "spring_mla_attention", "_mm_kernel.5": None}


def _op(name, dur_ns):
    return tracing.Op(device="/device:TPU:0", name=name, start_ns=0.0, dur_ns=float(dur_ns),
                      module="jit_plain_step", text=name)


def _run(report, trace=True):
    """Two steps on one chip whose layer report is ``report``."""
    ops = [_op("fusion.1", 2e6), _op("fusion.2", 4e6), _op("fusion.3", 1e6),
           _op("fusion.4", 6e6), _op("_mm_kernel.5", 9e6)]
    tr = tracing.Trace(ops=ops, spans=[], window=(0.0, 1e9)) if trace else None
    return NS(cell=None, trace=tr, counters={"steps": 2}, chips=1, layer_report=report)


REPORT = {"module": "jit_plain_step", "scopes": layer_probe.instruction_scopes(HLO),
          "moe_rows": [384.0, 4096.0, 0.0]}
READERS = ["moe_route_ms_per_step.train", "mla_attention_ms_per_step.train",
           "moe_live_rows.train"]


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name,want", [("moe_route_ms_per_step.train", 3.0),
                                       ("mla_attention_ms_per_step.train", 3.0),
                                       ("moe_live_rows.train", 9.375)])
def test_readers_on_a_synthetic_run(name, want):
    assert _reader(name).read(_run(REPORT)) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_what_they_read(name):
    """None without a report, without the scopes and counter (a program
    without them), and for the time readers without a trace; the row
    share is None where a pair was dropped."""
    reader = _reader(name)
    assert reader.read(_run(None)) is None
    bare = {"module": "jit_plain_step", "scopes": {"fusion.1": None}, "moe_rows": None}
    assert reader.read(_run(bare)) is None
    if name == "moe_live_rows.train":
        assert reader.read(_run(dict(REPORT, moe_rows=[380.0, 4096.0, 4.0]))) is None
    else:
        assert reader.read(_run(REPORT, trace=False)) is None


def test_scope_seconds_splits_the_step_module():
    got = layer_probe.scope_seconds(_run(REPORT).trace, REPORT)
    assert got["spring_moe_dispatch"] == 2e-3 and got["spring_moe_combine"] == 4e-3
    assert got["spring_mla_attention"] == 6e-3 and got["spring_quantize"] == 1e-3
    assert got["_mm_kernel"] == 9e-3 and got["unmapped"] == 0.0


# -- a tiny DeepSeek cell, whole runs on the CPU ----------------------------

TINY = dict(
    {k: v for k, v in CONFIG.items() if k not in ("program", "cut")},
    hidden_size=64, intermediate_size=160, kv_lora_rank=32, moe_intermediate_size=48,
    n_routed_experts=4, num_attention_heads=4, num_experts_per_tok=2, num_hidden_layers=3,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=512,
    published={"n_routed_experts": 8},
    program={"arch": "deepseek-v2-lite-16b", "preset": "reduced",
             "replace": {"experts_held": 4},
             "expect": {"d_model": 64, "n_layers": 3, "vocab": 512, "experts_held": 4,
                        "moe.n_experts": 8, "moe.top_k": 2, "mla.kv_lora_rank": 32,
                        "mla.rope_scaling.factor": 40.0, "moe.norm_topk": False}})

#: limits of the tiny cell, from its CPU readings (seeds 1-3): sound loss
#: 4.4e-5..9.3e-5, grad 6.1e-5..8.5e-5, change 1.0e-4..2.2e-4; the int8
#: control loss 8.4e-3..1.8e-2, grad 1.7e-2..2.4e-2, change 8.6e-3..9.5e-3;
#: half the batch above both
TINY_LIMITS = {"loss_gap": 1e-3, "grad_gap": 2e-3, "change_gap": 2e-3}


@pytest.fixture
def tiny_deepseek(tmp_path):
    """A checkout-like tree with the benchmark's files, the program's
    sources and one tiny DeepSeek cell made of new files and entries."""
    bench = tmp_path / "benchmarks" / "spring_bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "src").symlink_to(harness.ROOT / "src")
    traffic = dict(harness.load_json(BENCH / "traffic" / "train.qs.4k.json"), batch=2, seq=64)
    for rel, obj in {"configs/tiny-deepseek.json": TINY, "traffic/tiny.4k.json": traffic,
                     "checks/tiny.deepseek.json": {"limits": TINY_LIMITS}}.items():
        (bench / rel).write_text(json.dumps(obj))
    for kind in ("reference", "work"):
        shutil.copy(bench / kind / "deepseek-v2-lite.py", bench / kind / "tiny-deepseek.py")
    manifest = harness.load_json(harness.ROOT / "BENCHMARK.json")
    manifest["configs"] = [{"name": "tiny-deepseek", "source": "test", "reduced": [],
                            "file": "benchmarks/spring_bench/configs/tiny-deepseek.json",
                            "why": "test"}]
    manifest["workloads"] = [{"name": "tiny.deepseek", "config": "tiny-deepseek",
                              "traffic": "tiny.4k", "chips": 1, "why": "test"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.deepseek"] if CELL in m["workloads"] else []
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp_path / "BENCHMARK.json", bench


@pytest.fixture
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    yield
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    compilation_cache.reset_cache()


def test_a_traced_run_of_a_tiny_cell(tiny_deepseek, no_persistent_cache):
    """The cell's whole run on the CPU: correct, and the counter's row
    share read after the window (the device readers find no TPU ops)."""
    manifest, bench = tiny_deepseek
    r = run_cell.run("tiny.deepseek", 2**33 + 11, 0.5, True, manifest=manifest,
                     bench_dir=bench, require_chip=False, peaks_kind="TPU v5 lite")
    assert r["correct"] is True and r["failed"] == 0, r["checks"]
    # 2 MoE layers x 4 held experts x 128 rows; 2 of 8 experts a token
    live = r["metrics"]["moe_live_rows.train"]["value"]
    assert 0.0 < live < 100.0
    assert "moe_route_ms_per_step.train" not in r["metrics"]


def test_controls_of_a_tiny_cell_fail_and_sound_runs_pass(tiny_deepseek, no_persistent_cache):
    manifest, bench = tiny_deepseek
    cell = harness.load_cell("tiny.deepseek", manifest, bench)
    rows = dict(controls.train_readings(cell, 3, ["sound", "control", "half_batch"]))
    within = {kind: harness.judge(rows[kind], TINY_LIMITS)[0] for kind in rows}
    assert within == {"sound": True, "control": False, "half_batch": False}, rows
