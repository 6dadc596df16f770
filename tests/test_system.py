"""End-to-end behaviour tests: training converges, resumes exactly from
checkpoints, serving generates, SR fixed-point training tracks fp32 (the
paper's central training claim), and the dry-run machinery works on a
small in-process mesh."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.train import train_loop


def test_training_loss_decreases(tmp_path):
    res = train_loop("llama3.2-1b", reduced=True, steps=40, batch=8, seq=64,
                     ckpt_dir=str(tmp_path), ckpt_every=20)
    assert res["last_loss"] < res["first_loss"] - 0.5
    assert res["slow_steps"] <= 2


def test_checkpoint_resume_is_exact(tmp_path):
    """Train 20 steps with checkpointing, kill, resume to 30; compare with
    an uninterrupted 30-step run — losses must match exactly (determinism
    across restart is the fault-tolerance contract)."""
    a = train_loop("llama3.2-1b", reduced=True, steps=30, batch=4, seq=32)
    train_loop("llama3.2-1b", reduced=True, steps=20, batch=4, seq=32,
               ckpt_dir=str(tmp_path), ckpt_every=10)
    b = train_loop("llama3.2-1b", reduced=True, steps=30, batch=4, seq=32,
                   ckpt_dir=str(tmp_path), ckpt_every=10)
    np.testing.assert_allclose(a["losses"][-1], b["losses"][-1], rtol=1e-4)


def test_sr_fixed_point_training_tracks_fp32():
    """Gupta'15 / paper §6: Q4.16 + stochastic rounding trains ~like fp32."""
    fp32 = train_loop("llama3.2-1b", reduced=True, steps=60, batch=8, seq=64, mode="dense")
    srq = train_loop("llama3.2-1b", reduced=True, steps=60, batch=8, seq=64,
                     mode="quant", fixed_point_weights=True)
    assert srq["last_loss"] < srq["first_loss"] - 0.3, "SR training must learn"
    assert srq["last_loss"] < fp32["last_loss"] + 0.6, (
        f"SR-fixed-point diverged from fp32: {srq['last_loss']} vs {fp32['last_loss']}")


def test_serving_generates_finite_tokens():
    from repro.launch.serve import serve_session

    out = serve_session("llama3.2-1b", reduced=True, batch=2, prompt_len=12, gen=6)
    assert out["finite"]
    assert out["generated"].shape == (2, 6)


def test_compressed_allreduce_int8_error_feedback():
    """int8+EF gradient reduction: single-shard semantics (mean==identity)
    and error feedback captures exactly the quantization residual."""
    from repro.runtime.compression import (
        compressed_allreduce_tree,
        dequantize_int8,
        sr_quantize_int8,
    )

    mesh = jax.make_mesh((1,), ("pod",))
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (64,))}

    from jax.sharding import PartitionSpec as P

    def run(grads):
        return compressed_allreduce_tree(grads, "pod", jax.random.PRNGKey(1))

    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(jax.tree_util.tree_map(lambda _: P(), g),),
                       out_specs=(jax.tree_util.tree_map(lambda _: P(), g),) * 2,
                       check_vma=False)
    out, ef = fn(g)
    # mean over 1 shard == dequantized value; residual = original - dequant
    np.testing.assert_allclose(np.asarray(out["w"] + ef["w"]), np.asarray(g["w"]),
                               rtol=1e-5, atol=1e-6)
    # quantization error bounded by one int8 step
    q, scale = sr_quantize_int8(g["w"], jax.random.PRNGKey(2))
    err = np.abs(np.asarray(g["w"] - dequantize_int8(q, scale)))
    assert err.max() <= float(scale) + 1e-7


@pytest.mark.slow
def test_dryrun_debug_mesh_subprocess():
    """The actual dry-run entrypoint on an 8-device debug mesh (full-size
    llama decode cell): lower + compile + analyses must succeed."""
    env = dict(os.environ,
               REPRO_DRYRUN_XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "llama3.2-1b",
         "--shape", "decode_32k", "--mesh", "debug", "--mode", "dense",
         "--no-unrolled-cost"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    payload = out.stdout[out.stdout.find("{"):]
    result = json.loads(payload[: payload.rfind("}") + 1])
    assert result["status"] == "ok"
    assert result["memory"]["peak_bytes_per_chip_est"] > 0
